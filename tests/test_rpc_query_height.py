"""`/abci_query` beside a block being applied (`rpc/core.py`,
`abci/client.py` `CommittedHeight`): a block is in the store, and in
`/status`, before it is applied, and a query that arrives in between
waits for the app's `Commit` of that block. The signal is the `Commit`
that returns on the consensus connection, not a height of the state,
which moves earlier (and, pipelined, before the apply has run at all).
"""

from __future__ import annotations

import threading
from types import SimpleNamespace

import pytest

from tendermint_tpu.abci import local_client_creator
from tendermint_tpu.abci.apps import KVStoreApp
from tendermint_tpu.abci.socket import ABCISocketServer, socket_client_creator
from tendermint_tpu.rpc import core
from tendermint_tpu.rpc.server import RPCError


def _node(conns, stored: int, state_height: int):
    """The surface `abci_query` reads."""
    return SimpleNamespace(
        config=SimpleNamespace(rpc=SimpleNamespace(unsafe=False)),
        block_store=SimpleNamespace(height=stored),
        current_state=SimpleNamespace(last_block_height=state_height),
        app_conns=conns,
    )


def _block(conns, height: int, commit: bool = True) -> None:
    """What `apply_block` sends the app for a block that writes its height."""
    conns.consensus.deliver_tx_async(b"k=written at %d" % height)
    conns.consensus.end_block_sync(height)
    if commit:
        conns.consensus.commit_sync()


def _value(node) -> bytes:
    return bytes.fromhex(core.make_routes(node)["abci_query"](data=b"k".hex())["value"])


@pytest.fixture(params=["local", "socket"])
def conns(request):
    if request.param == "local":
        yield local_client_creator(KVStoreApp())()
        return
    server = ABCISocketServer(KVStoreApp(), "tcp://127.0.0.1:0")
    conns = socket_client_creator(f"127.0.0.1:{server.port}")()
    try:
        yield conns
    finally:
        conns.close()
        server.stop()


@pytest.mark.parametrize("state_height", [7, 8, 9], ids=["behind", "set", "provisional"])
def test_a_query_beside_a_block_being_applied_waits_for_its_commit(conns, state_height):
    """Block 8 is stored and delivered, its `Commit` has not returned:
    wherever the state's height stands (`apply_block` raises it before
    the commit, the pipelined tail before the apply), the query waits."""
    _block(conns, 7)
    _block(conns, 8, commit=False)
    node = _node(conns, stored=8, state_height=state_height)
    got = []
    asking = threading.Thread(target=lambda: got.append(_value(node)))
    asking.start()
    asking.join(0.2)
    assert asking.is_alive() and not got
    conns.consensus.commit_sync()
    asking.join(10)
    assert got == [b"written at 8"]


def test_a_node_that_is_level_does_not_wait(conns, monkeypatch):
    monkeypatch.setattr(core, "APPLY_WAIT_S", 0.0)
    _block(conns, 8)
    assert _value(_node(conns, stored=8, state_height=8)) == b"written at 8"


def test_a_node_that_has_committed_nothing_yet_does_not_wait(conns, monkeypatch):
    """After a restart the handshake has levelled the app with the store."""
    monkeypatch.setattr(core, "APPLY_WAIT_S", 0.0)
    assert conns.consensus.committed.height is None
    assert _value(_node(conns, stored=8, state_height=8)) == b""


def test_a_node_whose_apply_failed_says_so_and_gives_no_stale_answer(conns, monkeypatch):
    monkeypatch.setattr(core, "APPLY_WAIT_S", 0.05)
    _block(conns, 7)
    with pytest.raises(RPCError, match="block 8 is stored and the app has committed 7"):
        _value(_node(conns, stored=8, state_height=7))
