"""Non-loopback address-table validation (round-3 verdict item 6).

Everything previously ran on a single 127.0.0.1: bind and advertised
addresses were conflated, and DMLC_NODE_HOST / DMLC_INTERFACE were
parsed but never exercised. These tests pin the reference semantics
(van.cc:427-477, docs/source/multi-host-deployment.rst): a van binds
0.0.0.0 and ADVERTISES its DMLC_NODE_HOST; DMLC_INTERFACE names a NIC
whose resolved IP is both bound and advertised; and a full 12-process
HiPS topology runs with each party on a DISTINCT address
(127.0.0.2/3/4 — Linux routes all of 127/8 to loopback, giving three
genuinely different addresses in the node tables without root).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from geomx_tpu.config import Config, resolve_interface_ip  # noqa: E402


def test_interface_resolution_lo():
    assert resolve_interface_ip("lo") == "127.0.0.1"


def test_interface_resolution_unknown_raises():
    with pytest.raises(ValueError, match="DMLC_INTERFACE"):
        resolve_interface_ip("no-such-nic0")


def test_node_addr_rules():
    # DMLC_NODE_HOST not locally bindable (NAT/VIP): bind everything,
    # advertise the named address
    assert Config(node_host="10.1.2.3").node_addr() == \
        ("0.0.0.0", "10.1.2.3")
    # locally bindable DMLC_NODE_HOST: bind it directly (no wildcard
    # listener on shared hosts)
    assert Config(node_host="127.0.0.2").node_addr() == \
        ("127.0.0.2", "127.0.0.2")
    # DMLC_INTERFACE: resolved IP both ways
    assert Config(interface="lo").node_addr() == \
        ("127.0.0.1", "127.0.0.1")
    # node_host wins over interface (most specific)
    assert Config(node_host="10.1.2.3", interface="lo").node_addr() == \
        ("0.0.0.0", "10.1.2.3")
    # neither: loopback
    assert Config().node_addr() == ("127.0.0.1", "127.0.0.1")


def test_van_refuses_unadvertisable_bind():
    from geomx_tpu.ps.message import Role
    from geomx_tpu.ps.van import Van

    with pytest.raises(ValueError, match="advertise"):
        Van(my_role=Role.WORKER, is_global=False, root_uri="127.0.0.1",
            root_port=1, num_workers=1, num_servers=1,
            bind_host="0.0.0.0")


def test_two_party_topology_across_distinct_addresses():
    """In-process 2-node rendezvous across two DIFFERENT addresses: the
    scheduler advertises 127.0.0.2 (bound 0.0.0.0), the worker
    advertises 127.0.0.3 — the broadcast node table must carry the
    advertised addresses and messages must flow both ways."""
    import threading

    from geomx_tpu.ps import base as psbase
    from geomx_tpu.ps.message import Role
    from geomx_tpu.ps.postoffice import Postoffice
    from geomx_tpu.simulate import free_port

    port = free_port()
    boxes = {}

    def node(role, node_host, nw):
        cfg = Config(node_host=node_host)
        po = Postoffice(my_role=role, is_global=False,
                        root_uri="127.0.0.2", root_port=port,
                        num_workers=nw, num_servers=0, cfg=cfg)
        po.start(60.0)
        boxes[role] = po
        po.barrier(psbase.ALL_GROUP, timeout=60.0)

    ts = [threading.Thread(target=node, args=(Role.SCHEDULER, "127.0.0.2", 1),
                           daemon=True),
          threading.Thread(target=node, args=(Role.WORKER, "127.0.0.3", 1),
                           daemon=True)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(90)
    assert not any(t.is_alive() for t in ts), "rendezvous hung"
    try:
        wtable = boxes[Role.WORKER].van.node_table
        hosts = {h for h, _ in wtable.values()}
        assert hosts == {"127.0.0.2", "127.0.0.3"}, wtable
    finally:
        for po in boxes.values():
            po.van.stop()


@pytest.mark.slow
def test_hips_launch_across_three_addresses():
    """The full 12-process HiPS demo with every party on its own
    address (central 127.0.0.2, parties 127.0.0.3/4): nodes bind
    0.0.0.0, advertise DMLC_NODE_HOST, cross-address WAN + LAN tiers
    train and exit clean."""
    from tests.harness import _run_launch

    accs = _run_launch(
        "run_vanilla_hips.sh", [], n_iters=15, timeout=300,
        env_extra={"HOST_CENTRAL": "127.0.0.2", "HOST_A": "127.0.0.3",
                   "HOST_B": "127.0.0.4"})
    assert max(accs[-5:]) > 0.4, f"multi-address run did not learn: {accs}"


