"""The serving peer: a child process held to the CPU.

    python -m benchmark.lib.peer --home H --config C --mix M --seed S --blocks N
        [--index I --of P]

Generates the chain into `H` (or finds the one an earlier run with the
same key left there: `record.json` is written last), then serves it over
the program's own p2p stack (`Switch`, `TcpListener`, secret connections,
the blockchain channel 0x40) from memory: a block request is answered
with the bytes the generator wrote, a status request with the chain's
height. It is a fixture, not the system under test: a full node in its
place spends its time loading and re-encoding 1,024-vote commits for a
gossip the syncing node ignores, serves under three blocks a second, and
is evicted by the syncing node's 15-second request timeout (PERF.md,
Findings). Prints `peer up: p2p :<port>`, serves until SIGTERM.

A deployment of P serving peers is P such children over the one home
(`--index I --of P`; one peer gets neither option and is peer 0 of 1):
the first generates, the others wait for its `record.json` and read its
`blocks.bin`; each has a node key (`peers.key_file`), a port and a
`peer up:` line of its own. What a peer answers at a height is the mix's
peer rule (`lib/peers.py`; none: the generator's bytes): the unsound
answers are made once, here, before the peer is up, and each one given
is a row of the log, `lied <answer>: height <h> at <wall time>`. A peer
of several, or under a rule, says at its end which heights it served.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

BLOCKCHAIN_CHANNEL = 0x40
MSG_BLOCK_REQUEST, MSG_BLOCK_RESPONSE, MSG_NO_BLOCK = 0x01, 0x02, 0x03
MSG_STATUS_REQUEST, MSG_STATUS_RESPONSE = 0x04, 0x05
GENERATION_WAIT_S = 900.0  # what the driver gives the first peer to be up


def unsound(blocks: list[bytes], answers: dict[int, str], seed: int) -> dict[int, bytes | None]:
    """height -> the bytes a peer sends in place of the generator's (None:
    nothing) for each of its unsound `answers`. A `flip_sig` block is the
    generator's with one seeded signature bit of its `last_commit`
    flipped (`chain.tamper`); the header is left as it was."""
    from benchmark.lib import chain
    from tendermint_tpu.types.block import Block

    out: dict[int, bytes | None] = {}
    for height, answer in answers.items():
        if answer == "silent":
            out[height] = None
            continue
        block = Block.decode(blocks[height - 1])
        if not any(v is not None for v in block.last_commit.precommits):
            raise ValueError(f"flip_sig at height {height}: the block's last_commit holds no signature")
        block.last_commit, _lane = chain.tamper(block.last_commit, seed + height)
        out[height] = block.encode()
    return out


def serve(home: str, chain_id: str, rate: int, key_file: str, answers=None, seed: int = 0, served=None):
    """Start the switch; returns (switch, listener). With `served`, a list
    that takes every height the generator's bytes went out for, the peer
    also answers the heights of `answers` (`peers.answers`) unsoundly."""
    from benchmark.lib import chain
    from tendermint_tpu.codec.binary import Reader, Writer
    from tendermint_tpu.p2p.connection import ChannelDescriptor
    from tendermint_tpu.p2p.node_key import NodeKey
    from tendermint_tpu.p2p.peer import NodeInfo
    from tendermint_tpu.p2p.switch import Reactor, Switch
    from tendermint_tpu.p2p.tcp import TcpListener

    blocks = chain.read_blocks(home)
    responses = [
        Writer().uvarint(MSG_BLOCK_RESPONSE).bytes(b).build() for b in blocks
    ]
    status = Writer().uvarint(MSG_STATUS_RESPONSE).uvarint(len(blocks)).build()
    lies = unsound(blocks, answers, seed) if answers else {}

    class ServeBlocks(Reactor):
        def get_channels(self):
            return [ChannelDescriptor(BLOCKCHAIN_CHANNEL, priority=5, send_queue_capacity=256)]

        def add_peer(self, peer) -> None:
            peer.try_send(BLOCKCHAIN_CHANNEL, status)

        def receive(self, chan_id: int, peer, payload: bytes) -> None:
            r = Reader(payload)
            tag = r.uvarint()
            if tag == MSG_BLOCK_REQUEST:
                height = r.uvarint()
                if 1 <= height <= len(responses):
                    peer.send(BLOCKCHAIN_CHANNEL, responses[height - 1])
                else:
                    peer.try_send(
                        BLOCKCHAIN_CHANNEL, Writer().uvarint(MSG_NO_BLOCK).uvarint(height).build()
                    )
            elif tag == MSG_STATUS_REQUEST:
                peer.try_send(BLOCKCHAIN_CHANNEL, status)

    class ServeByRule(ServeBlocks):
        """A peer of several, or one under a rule: it notes what it
        serves, and answers the rule's heights with the rule's bytes."""

        def receive(self, chan_id: int, peer, payload: bytes) -> None:
            r = Reader(payload)
            if r.uvarint() != MSG_BLOCK_REQUEST:
                return super().receive(chan_id, peer, payload)
            height = r.uvarint()
            if height not in lies:
                if 1 <= height <= len(responses):
                    served.append(height)
                return super().receive(chan_id, peer, payload)
            lie = lies[height]
            print(f"lied {'silent' if lie is None else 'flip_sig'}: height {height} at {time.time():.3f}", flush=True)
            if lie is not None:
                peer.send(BLOCKCHAIN_CHANNEL, Writer().uvarint(MSG_BLOCK_RESPONSE).bytes(lie).build())

    key = NodeKey.load_or_gen(os.path.join(home, key_file))
    switch = Switch(NodeInfo(node_id=key.node_id, moniker="peer", chain_id=chain_id))
    switch.send_rate = switch.recv_rate = rate
    switch.add_reactor("blockchain", ServeBlocks() if served is None else ServeByRule())
    listener = TcpListener(switch, "tcp://127.0.0.1:0", priv_key=key.priv_key, start=False)
    switch.listen_addr = f"127.0.0.1:{listener.port}"
    switch.start()
    listener.start_accepting()
    return switch, listener


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--home", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--blocks", type=int, required=True)
    ap.add_argument("--workers", type=int, default=0)
    ap.add_argument("--index", type=int, default=0, help="which of the deployment's serving peers this is")
    ap.add_argument("--of", type=int, default=1, help="how many serving peers the deployment has")
    args = ap.parse_args(argv)

    from benchmark.lib import chain, peers

    with open(args.config) as f:
        config = json.load(f)
    with open(args.mix) as f:
        mix = json.load(f)
    record_path = os.path.join(args.home, "record.json")
    if args.index and not os.path.exists(record_path):
        # the first peer generates: its record is written last
        deadline = time.monotonic() + GENERATION_WAIT_S
        while not os.path.exists(record_path):
            if time.monotonic() > deadline:
                print(f"peer {args.index}: no {record_path} after {GENERATION_WAIT_S:.0f}s", flush=True)
                return 1
            time.sleep(0.2)
    if os.path.exists(record_path):
        print(f"chain ready: cached at {args.home}", flush=True)
    else:
        record = chain.build_chain(
            config, mix, args.seed, args.blocks, args.home, args.workers
        )
        record.save(record_path)
        print(
            f"chain ready: {args.blocks} blocks in {record.build_seconds:.1f}s "
            f"({chain.digest(record)})",
            flush=True,
        )
    answers, served = {}, None
    if peers.keeps_notes(mix, args.of):
        answers, served = peers.answers(mix, args.index, args.of, args.blocks), []
        print(f"peer {args.index} of {args.of}: {len(answers)} heights answered unsoundly", flush=True)
    switch, listener = serve(
        args.home, chain.CHAIN_ID, int(config["p2p_rate_bytes_per_s"]), peers.key_file(args.index),
        answers=answers, seed=args.seed, served=served,
    )
    print(f"peer up: p2p :{listener.port} height {args.blocks}", flush=True)
    stop: list[int] = []
    signal.signal(signal.SIGTERM, lambda *_a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *_a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        listener.stop()
        switch.stop()
    if served is not None:
        print(f"served: {json.dumps(served)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
