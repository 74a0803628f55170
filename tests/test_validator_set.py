import pytest

from tendermint_tpu.types import ValidationError, Validator, ValidatorSet
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_validators


def test_sorted_by_address():
    vs, _ = make_validators(10)
    addrs = [v.address for v in vs.validators]
    assert addrs == sorted(addrs)
    assert vs.total_voting_power == 100


def test_proposer_rotation_equal_power_cycles():
    vs, _ = make_validators(4)
    seen = []
    for _ in range(8):
        vs.increment_accum(1)
        seen.append(vs.proposer.address)
    # equal power: each validator proposes twice over 8 rounds
    from collections import Counter

    counts = Counter(seen)
    assert all(c == 2 for c in counts.values())


def test_proposer_rotation_weighted():
    _, privs = make_validators(3)
    vals = [
        Validator(address=p.address, pub_key=p.pub_key, voting_power=w)
        for p, w in zip(privs, [1, 1, 8])
    ]
    vs = ValidatorSet(vals)
    heavy = vals[2].address
    from collections import Counter

    seen = Counter()
    for _ in range(10):
        vs.increment_accum(1)
        seen[vs.proposer.address] += 1
    assert seen[heavy] == 8


def test_hash_changes_with_membership():
    vs, _ = make_validators(4)
    h1 = vs.hash()
    vs2, _ = make_validators(5)
    assert h1 != vs2.hash()
    assert len(h1) == 32


def test_verify_commit_ok():
    vs, privs = make_validators(4)
    bid = make_block_id()
    commit = make_commit(vs, privs, height=5, round_=0, block_id=bid)
    vs.verify_commit(CHAIN_ID, bid, 5, commit)  # no raise


def test_verify_commit_insufficient_power():
    vs, privs = make_validators(4)
    bid = make_block_id()
    # only 2 of 4 sign -> 50% < 2/3... but make_commit needs maj23; build by hand
    from tests.helpers import signed_vote
    from tendermint_tpu.types import VOTE_TYPE_PRECOMMIT, Commit

    votes = [None] * 4
    for i in range(2):
        votes[i] = signed_vote(privs[i], i, 5, 0, VOTE_TYPE_PRECOMMIT, bid)
    commit = Commit(block_id=bid, precommits=votes)
    with pytest.raises(ValidationError, match="insufficient"):
        vs.verify_commit(CHAIN_ID, bid, 5, commit)


def test_verify_commit_bad_signature():
    vs, privs = make_validators(4)
    bid = make_block_id()
    commit = make_commit(vs, privs, height=5, round_=0, block_id=bid)
    # corrupt one signature
    v = commit.precommits[0]
    commit.precommits[0] = v.with_signature(bytes(64))
    with pytest.raises(ValidationError, match="signature"):
        vs.verify_commit(CHAIN_ID, bid, 5, commit)


def test_verify_commit_wrong_height():
    vs, privs = make_validators(4)
    bid = make_block_id()
    commit = make_commit(vs, privs, height=5, round_=0, block_id=bid)
    with pytest.raises(ValidationError):
        vs.verify_commit(CHAIN_ID, bid, 6, commit)


def test_verify_commit_any_small_change():
    vs, privs = make_validators(4)
    bid = make_block_id()
    commit = make_commit(vs, privs, height=7, round_=0, block_id=bid)
    # old set == new set works through verify_commit_any too
    vs.verify_commit_any(vs, CHAIN_ID, bid, 7, commit)


def test_apply_changes():
    vs, privs = make_validators(4)
    target = vs.validators[0]
    vs.apply_changes([Validator(target.address, target.pub_key, 0)])
    assert vs.size() == 3
    assert not vs.has_address(target.address)
    # update power
    v1 = vs.validators[0]
    vs.apply_changes([Validator(v1.address, v1.pub_key, 99)])
    assert vs.get_by_address(v1.address)[1].voting_power == 99


def test_duplicate_address_rejected():
    vs, _ = make_validators(2)
    with pytest.raises(ValidationError):
        ValidatorSet(list(vs.validators) + [vs.validators[0]])


def test_verify_commit_any_requires_new_set_quorum():
    # Old set: 4 validators of 10. New set: same 4 plus a whale of 120.
    # A commit signed by the original 4 has >2/3 of OLD power but only
    # 40/160 of NEW power -> must be rejected (reference :340-346 rule).
    from tests.helpers import det_priv_keys
    from tendermint_tpu.types import PrivValidator

    vs, privs = make_validators(4)
    whale_priv = PrivValidator(det_priv_keys(5)[4])
    new_vals = list(vs.validators) + [
        Validator(whale_priv.address, whale_priv.pub_key, 120)
    ]
    new_vs = ValidatorSet(new_vals)
    bid = make_block_id()
    # commit shaped for the NEW set (5 slots), signed only by the old 4
    from tests.helpers import signed_vote
    from tendermint_tpu.types import VOTE_TYPE_PRECOMMIT, Commit

    precommits = [None] * new_vs.size()
    for i, val in enumerate(new_vs.validators):
        idx, old = vs.get_by_address(val.address)
        if old is None:
            continue
        p = next(p for p in privs if p.address == val.address)
        precommits[i] = signed_vote(p, i, 9, 0, VOTE_TYPE_PRECOMMIT, bid)
    commit = Commit(block_id=bid, precommits=precommits)
    with pytest.raises(ValidationError, match="new voting power"):
        vs.verify_commit_any(new_vs, CHAIN_ID, bid, 9, commit)


# -- a block rotates the set, it does not rebuild it --------------------------
#
# copy() hands on what membership alone decides (root, address index, total
# power, the static half of the JSON) and increment_accum touches only the
# accumulators. Nothing a caller can observe may change with that.

ROTATION_POWERS = {
    "linear": [20 + 10 * i for i in range(100)],  # the benchmark's ToValidators(20, 10)
    "uniform": [10] * 7,  # ties at every step
    "one_whale": [1, 1, 1, 1, 1000],
}


def _set_with_powers(powers) -> ValidatorSet:
    base, _ = make_validators(len(powers))
    return ValidatorSet(
        [Validator(v.address, v.pub_key, p) for v, p in zip(base.validators, powers)]
    )


def _reference_rotation(rows: list[list], total: int) -> bytes:
    """One `IncrementAccum` step written plainly over [address, power,
    accum] rows (sorted by address): returns the proposer's address."""
    for row in rows:
        row[2] += row[1]
    best = rows[0]
    for row in rows[1:]:
        if row[2] > best[2] or (row[2] == best[2] and row[0] < best[0]):
            best = row
    best[2] -= total
    return best[0]


@pytest.mark.parametrize("powers", sorted(ROTATION_POWERS))
def test_a_thousand_rotations_match_a_plain_reference(powers):
    vs = _set_with_powers(ROTATION_POWERS[powers])
    rows = [[v.address, v.voting_power, v.accum] for v in vs.validators]
    total = sum(ROTATION_POWERS[powers])
    for step in range(1000):
        vs = vs.copy()  # as a block does: rotate a copy of the last set
        vs.increment_accum(1)
        want = _reference_rotation(rows, total)
        assert vs.proposer.address == want, step
        assert [v.accum for v in vs.validators] == [r[2] for r in rows], step
        _, held = vs.get_by_address(want)
        assert held is vs.proposer
    assert sum(v.accum for v in vs.validators) == 0


@pytest.mark.parametrize("powers", sorted(ROTATION_POWERS))
def test_rotating_k_times_at_once_is_k_rotations(powers):
    at_once = _set_with_powers(ROTATION_POWERS[powers])
    stepwise = at_once.copy()
    for k in (0, 1, 3, 17):
        at_once.increment_accum(k)
        for _ in range(k):
            stepwise.increment_accum(1)
        assert at_once.validators == stepwise.validators
        if k:
            assert at_once.proposer == stepwise.proposer


def _observed(vs: ValidatorSet) -> tuple:
    return (
        vs.hash(),
        vs.total_voting_power,
        vs.proposer,
        list(vs.validators),
        [vs.get_by_address(v.address) for v in vs.validators],
        vs.to_json(),
    )


@pytest.mark.parametrize("changed", ["the_copy", "the_source"])
def test_a_copy_and_its_source_change_apart(changed):
    from tests.helpers import det_priv_keys

    source, _ = make_validators(6)
    source.increment_accum(2)
    _observed(source)  # everything a copy shares is built before the copy
    copy = source.copy()
    assert _observed(copy) == _observed(source)
    mover, still = (copy, source) if changed == "the_copy" else (source, copy)
    before = _observed(still)

    mover.increment_accum(3)
    assert _observed(still) == before
    newcomer = det_priv_keys(7)[6].pub_key
    gone, repowered = mover.validators[0], mover.validators[1]
    mover.apply_changes(
        [
            Validator(newcomer.address, newcomer, 5),
            Validator(gone.address, gone.pub_key, 0),
            Validator(repowered.address, repowered.pub_key, 77),
        ]
    )
    mover.increment_accum(1)
    assert _observed(still) == before
    assert still.has_address(gone.address) and not still.has_address(newcomer.address)

    # and the set that moved reads what a set built from its members reads
    rebuilt = ValidatorSet(list(mover.validators))
    assert mover.hash() == rebuilt.hash() != before[0]
    assert mover.total_voting_power == rebuilt.total_voting_power == 60 - 10 - 10 + 5 + 77
    assert mover.to_json() == rebuilt.to_json()
    assert not mover.has_address(gone.address)
    for i, v in enumerate(mover.validators):
        assert mover.get_by_address(v.address) == (i, v)


@pytest.mark.parametrize("fault", ["duplicate_address", "negative_power", "negative_change"])
def test_outside_data_is_still_checked(fault):
    vs, _ = make_validators(3)
    v = vs.validators[1]
    with pytest.raises(ValidationError):
        if fault == "duplicate_address":
            ValidatorSet(list(vs.copy().validators) + [v])
        elif fault == "negative_power":
            ValidatorSet([Validator(v.address, v.pub_key, -1)])
        else:
            # copy() no longer walks the set, so the one place a power
            # changes refuses what the constructor refuses
            vs.copy().apply_changes([Validator(v.address, v.pub_key, -1)])


def test_an_unsorted_list_is_sorted_and_an_empty_set_has_no_proposer():
    vs, _ = make_validators(5)
    shuffled = ValidatorSet(list(reversed(vs.validators)))
    assert shuffled.validators == vs.validators and shuffled.hash() == vs.hash()
    empty = ValidatorSet([]).copy()
    assert empty.size() == 0 and empty.total_voting_power == 0
    assert empty.to_json() == '{"validators": []}'
    with pytest.raises(ValidationError):
        empty.increment_accum(1)
