"""The serving peer: a child process held to the CPU.

    python -m benchmark.lib.peer --home H --config C --mix M --seed S --blocks N

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


def serve(home: str, chain_id: str, rate: int):
    """Start the switch; returns (switch, listener)."""
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

    key = NodeKey.load_or_gen(os.path.join(home, "peer_key.json"))
    switch = Switch(NodeInfo(node_id=key.node_id, moniker="peer", chain_id=chain_id))
    switch.send_rate = switch.recv_rate = rate
    switch.add_reactor("blockchain", ServeBlocks())
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
    args = ap.parse_args(argv)

    from benchmark.lib import chain

    with open(args.config) as f:
        config = json.load(f)
    with open(args.mix) as f:
        mix = json.load(f)
    record_path = os.path.join(args.home, "record.json")
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
    switch, listener = serve(args.home, chain.CHAIN_ID, int(config["p2p_rate_bytes_per_s"]))
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
