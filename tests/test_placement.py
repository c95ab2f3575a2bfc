"""Placement/cabling (§6) and fabric-aware mesh tests."""

import os
import subprocess
import sys
import pathlib

import numpy as np

from repro.core import fattree, fattree_equipment, jellyfish, plan_cables
from repro.core.placement import localized_jellyfish

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")


def test_cable_plan_counts_and_lengths():
    top = jellyfish(64, 10, 6, seed=0)
    plan = plan_cables(top)
    assert plan.n_cables == top.n_edges
    assert plan.n_server_cables == top.n_servers
    assert plan.max_length_m > 0
    # switch-cluster layout: all switch-switch cables have ~zero length
    assert plan.mean_length_m < 10.0


def test_jellyfish_fewer_cables_than_fattree():
    """§6.1: ~15% fewer cables at ~1000 servers — because the same server
    pool needs fewer SWITCHES at full capacity (same-equipment comparisons
    trivially tie: every port carries one cable)."""
    k = 16
    eq = fattree_equipment(k)
    ft = fattree(k)
    from repro.core import same_equipment_jellyfish

    jf = same_equipment_jellyfish(int(eq["switches"] * 0.82), k,
                                  eq["servers"], seed=0)
    total_ft = ft.n_edges + ft.n_servers
    total_jf = jf.n_edges + jf.n_servers
    assert jf.n_servers == ft.n_servers
    assert total_jf < total_ft * 0.87  # >= 13% fewer cables


def test_localized_jellyfish_cable_locality():
    top = localized_jellyfish(6, 10, 10, 8, local_links=5, seed=1)
    plan = plan_cables(top)
    assert 0.5 < plan.local_fraction < 0.75


def test_fabric_aware_mesh_subprocess():
    """Pod axis ordered by the ring embedding (needs >=8 fake devices)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
import sys
sys.path.insert(0, %r)
import numpy as np
from repro.fabric import make_fabric
from repro.launch.mesh import make_fabric_aware_mesh

fabric = make_fabric("jellyfish", n_pods=8, degree=4, seed=0)
mesh, order = make_fabric_aware_mesh(fabric, pods=8, per_pod_shape=(2, 2))
assert mesh.shape == {"pod": 8, "data": 2, "model": 2}, mesh.shape
assert sorted(order) == list(range(8))
print("OK")
""" % SRC
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": SRC}, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
