"""Validator-set rule `reactor_cycle`: the reference's own script for a
changing set, `consensus/reactor_test.go` `TestReactorValidatorSetChanges`
(v0.12; the mix's `assumed` says what is quoted from memory), one step
every `every` heights, in a cycle of five:

1. "adding one validator": standby key a joins at `standby_power`;
2. "changing the voting power of one validator": a goes to `updated_power`;
3. "adding two validators at once": b and c join at `standby_power`;
4. "removing two validators at once": b and c leave (power 0);
5. ours: a leaves, so the cycle closes on the genesis set and the set stays
   inside n_vals .. n_vals + 3 keys for any chain length.

Step k of the chain (k = 1, 2, ...) is the `val:` txs of the block at
height `every` * k. Cycle c (from 0) takes the three standby keys
n_vals + 3c .. n_vals + 3c + 2 and no others: a validator that left does
not come back under its old key. No cycle starts that would not close
before the chain's last `quiet_tail` heights, so the set of the tail is
the genesis set.

What the rule reads: the mix's `valset` object (`every`) over the
deployment's own numbers (`standby_power`, `updated_power`) and the
generator's `quiet_tail` (`chain.valset_rule`)."""

STEPS = 5


def changes(rule: dict, height: int, n_vals: int, n_blocks: int) -> list[tuple[int, int]]:
    """The (key rank, power) changes the block at `height` carries."""
    every = int(rule["every"])
    if height % every:
        return []
    cycle, step = divmod(height // every - 1, STEPS)
    if every * STEPS * (cycle + 1) > n_blocks - int(rule["quiet_tail"]):
        return []
    a, b, c = (n_vals + 3 * cycle + j for j in range(3))
    join, more = int(rule["standby_power"]), int(rule["updated_power"])
    return [
        [(a, join)],
        [(a, more)],
        [(b, join), (c, join)],
        [(b, 0), (c, 0)],
        [(a, 0)],
    ][step]
