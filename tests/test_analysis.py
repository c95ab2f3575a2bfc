"""repro.analysis (PR 6): invariant linter, runtime contracts, retrace tracer.

Three layers, three test groups:

* linter: every JF rule fires on a minimal bad fixture and stays silent on
  the corrected twin; the tree at HEAD lints clean (CI's lint lane in test
  form).
* contracts: each structural corruption of a PathSystem / PathSystemBatch /
  SimResult trips the matching check with a message naming the offending
  index, and the real builders (jellyfish / fat-tree / Clos / SWDC) plus a
  delta chain pass with checks forced on — no false positives.
* retrace: re-running a solved workload compiles nothing (the
  one-compile-per-shape-bucket guarantee), and the compile counter itself
  is live.
"""

import dataclasses
import functools
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro import env
from repro.analysis import (
    ContractViolation,
    RULES,
    check_path_system,
    check_path_system_batch,
    check_segment_layout,
    check_sim_state,
    lint_paths,
    lint_source,
    set_check_enabled,
)
from repro.core import (
    ClosSpec,
    build_clos,
    build_path_system,
    fail_links,
    fattree,
    jellyfish,
    random_permutation_traffic,
    swdc_ring,
    update_path_system,
)
from repro.core.flow import PathSystemBatch
from repro.sim import SimConfig, simulate, steady_poisson

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def checks_on():
    prev = set_check_enabled(True)
    try:
        yield
    finally:
        set_check_enabled(prev)


# --------------------------------------------------------------------------- #
# linter: rule fixtures
# --------------------------------------------------------------------------- #

# (rule, path-the-snippet-pretends-to-live-at, bad source, good source)
_RULE_FIXTURES = [
    (
        "JF001",
        "src/repro/core/routing.py",
        "order = hash((u, v))\n",
        "from .metrics import mix\norder = mix(u, v)\n",
    ),
    (
        "JF001",
        "src/repro/sim/ecmp.py",
        "seen = {1, 2}\nfor e in seen:\n    go(e)\n",
        "seen = {1, 2}\nfor e in sorted(seen):\n    go(e)\n",
    ),
    (
        "JF001",
        "src/repro/core/flow.py",
        "edges = set()\nrows = list(edges)\n",
        "edges = set()\nrows = sorted(edges)\n",
    ),
    (
        "JF002",
        "src/repro/core/routing.py",
        "import numpy as np\norder = np.argsort(keys)\n",
        'import numpy as np\norder = np.argsort(keys, kind="stable")\n',
    ),
    (
        "JF003",
        "src/repro/core/anywhere.py",
        'import os\nv = int(os.environ.get("REPRO_FOO", "1"))\n',
        'from repro import env\nv = env.read("REPRO_FOO")\n',
    ),
    (
        "JF003",
        "benchmarks/some_bench.py",
        'import os\nv = os.getenv("REPRO_BENCH_OUT")\n',
        'from repro import env\nv = env.read("REPRO_BENCH_OUT")\n',
    ),
    (
        "JF004",
        "src/repro/kernels/newkernel.py",
        (
            "def run(a, b):\n"
            "    a = jnp.pad(a, ((0, 4), (0, 0)))\n"
            "    return pl.pallas_call(_kernel, out_shape=sh)(a, b)\n"
        ),
        (
            "def run(a, b):\n"
            "    a, b = check_run_dtype(a, b)\n"
            "    a = jnp.pad(a, ((0, 4), (0, 0)))\n"
            "    return pl.pallas_call(_kernel, out_shape=sh)(a, b)\n"
        ),
    ),
    (
        "JF005",
        "src/repro/sim/engine.py",
        "total = jnp.sum(loads)\n",
        "total = _fold_sum(loads)\n",
    ),
    (
        "JF005",
        "src/repro/core/flow.py",
        'y = jnp.einsum("ps,p->s", inc, rates)\n',
        "y = _ordered_fan_in_sum(fr, table)\n",
    ),
    (
        "JF006",
        "src/repro/core/flow.py",
        (
            "def make_step(n_steps):\n"
            "    @jax.jit\n"
            "    def step(x):\n"
            "        return x * n_steps\n"
            "    return step\n"
        ),
        (
            '@functools.partial(jax.jit, static_argnames=("n_steps",))\n'
            "def step(x, n_steps):\n"
            "    return x * n_steps\n"
        ),
    ),
    (
        "JF006",
        "src/repro/sim/engine.py",
        "def warm(cfg):\n    return jax.jit(lambda x: x * cfg.dt)\n",
        "@jax.jit\ndef warm_step(x, dt):\n    return x * dt\n",
    ),
    (
        "JF000",
        "src/repro/core/flow.py",
        "x = 1  # repro-lint: disable=JF999\n",
        "x = 1  # repro-lint: disable=JF005\n",
    ),
    (
        "JF000",
        "src/repro/sim/engine.py",
        # comma lists are validated per id; IR rule ids (JF100-JF105) are
        # legitimate pragma targets even though the AST linter never fires
        # them itself
        "y = 2  # repro-lint: disable=JF005,JF01\n",
        "y = 2  # repro-lint: disable=JF005,JF104\n",
    ),
]


@pytest.mark.parametrize(
    "rule,path,bad,good",
    _RULE_FIXTURES,
    ids=[f"{r}-{i}" for i, (r, *_) in enumerate(_RULE_FIXTURES)],
)
def test_rule_fires_and_silences(rule, path, bad, good):
    fired = lint_source(bad, path)
    assert [v.rule for v in fired] == [rule]
    # the message is actionable: it names the rule and reads as guidance
    assert fired[0].line >= 1
    assert len(fired[0].message) > 20
    assert lint_source(good, path) == []


def test_rules_are_scoped():
    # JF001/JF002 only bind in routing/sim modules; JF005 only in the
    # solver files with a padded reduction axis; JF006 exempts the one-shot
    # launch drivers.  Out-of-scope twins of firing fixtures stay silent.
    assert lint_source("x = hash(y)\n", "src/repro/core/topology.py") == []
    assert (
        lint_source("import numpy as np\no = np.argsort(k)\n",
                    "src/repro/core/metrics.py")
        == []
    )
    assert lint_source("y = jnp.sum(x)\n", "src/repro/core/routing.py") == []
    assert (
        lint_source("def main():\n    f = jax.jit(lambda x: x)\n",
                    "src/repro/launch/serve.py")
        == []
    )


def test_pragma_suppresses():
    src = 'import numpy as np\no = np.argsort(k)  # repro-lint: disable=JF002\n'
    assert lint_source(src, "src/repro/core/routing.py") == []


def test_pragma_with_unknown_id_does_not_suppress():
    # a typo'd pragma must not silently disarm the rule it meant to name:
    # the original violation still fires, plus JF000 for the bad id
    src = 'import numpy as np\no = np.argsort(k)  # repro-lint: disable=JF02\n'
    rules = sorted(v.rule for v in lint_source(src, "src/repro/core/routing.py"))
    assert rules == ["JF000", "JF002"]
    # and JF000 cannot suppress itself
    src = "x = 1  # repro-lint: disable=JF999,JF000\n"
    assert [v.rule for v in lint_source(src, "src/repro/core/flow.py")] == [
        "JF000"
    ]


def test_tree_lints_clean_at_head():
    violations = lint_paths([str(ROOT / "src"), str(ROOT / "benchmarks")])
    assert violations == [], "\n".join(str(v) for v in violations)


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "src" / "repro" / "core" / "routing.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import numpy as np\norder = np.argsort(keys)\n")
    code = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(tmp_path)],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
    )
    assert code.returncode == 1
    assert "JF002" in code.stdout
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(ROOT / "benchmarks")],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr


def test_every_rule_has_a_fixture():
    assert {r for r, *_ in _RULE_FIXTURES} == set(RULES)


# --------------------------------------------------------------------------- #
# env registry
# --------------------------------------------------------------------------- #


def test_env_rejects_bad_values(monkeypatch):
    monkeypatch.setenv("REPRO_LP_PATH_LIMIT", "twenty")
    with pytest.raises(ValueError, match="REPRO_LP_PATH_LIMIT"):
        env.read("REPRO_LP_PATH_LIMIT")
    monkeypatch.setenv("REPRO_ROUTE_TILE_BYTES", "12")  # below 1 MiB floor
    with pytest.raises(ValueError, match="REPRO_ROUTE_TILE_BYTES"):
        env.read("REPRO_ROUTE_TILE_BYTES")
    monkeypatch.setenv("REPRO_APSP_BACKEND", "quantum")
    with pytest.raises(ValueError, match="REPRO_APSP_BACKEND"):
        env.read("REPRO_APSP_BACKEND")


def test_env_defaults_and_is_set(monkeypatch):
    monkeypatch.delenv("REPRO_LP_PATH_LIMIT", raising=False)
    assert env.read("REPRO_LP_PATH_LIMIT") == 20000
    assert not env.is_set("REPRO_LP_PATH_LIMIT")
    monkeypatch.setenv("REPRO_LP_PATH_LIMIT", "12345")
    assert env.read("REPRO_LP_PATH_LIMIT") == 12345
    assert env.is_set("REPRO_LP_PATH_LIMIT")
    with pytest.raises(KeyError):
        env.read("REPRO_NOT_A_REGISTERED_KNOB")


def test_env_validates_whole_registry_at_import(monkeypatch):
    # any repro import validates EVERY registered variable, so a typo'd
    # setting fails at startup instead of being read mid-sweep
    r = subprocess.run(
        [sys.executable, "-c", "import repro.env"],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
             "REPRO_SIM_MAX_STEPS": "0"},
    )
    assert r.returncode != 0
    assert "REPRO_SIM_MAX_STEPS" in r.stderr


# --------------------------------------------------------------------------- #
# contracts: corruptions fire, real builders pass
# --------------------------------------------------------------------------- #


def _small_ps():
    top = jellyfish(24, 8, 4, seed=3)
    comm = random_permutation_traffic(top, seed=4)
    return top, comm, build_path_system(top, comm, k=4)


def test_contract_out_of_range_slot(checks_on):
    _, _, ps = _small_ps()
    pe = ps.path_edges.copy()
    row = int(np.argmax(ps.path_len >= 1))
    pe[row, 0] = ps.n_slots + 7  # beyond even the padding sentinel
    bad = dataclasses.replace(ps, path_edges=pe)
    with pytest.raises(ContractViolation, match="directed slot"):
        check_path_system(bad)


def test_contract_wrong_padding_sentinel(checks_on):
    _, _, ps = _small_ps()
    lens = np.asarray(ps.path_len)
    rows = np.flatnonzero(lens < ps.path_edges.shape[1])
    assert rows.size, "need a row with padded columns"
    pe = ps.path_edges.copy()
    pe[rows[0], lens[rows[0]]] = 0  # valid slot id where the sentinel belongs
    bad = dataclasses.replace(ps, path_edges=pe)
    with pytest.raises(ContractViolation, match="beyond"):
        check_path_system(bad)


def test_contract_nonpositive_capacity(checks_on):
    _, _, ps = _small_ps()
    caps = ps.capacities.copy()
    caps[0] = 0.0
    bad = dataclasses.replace(ps, capacities=caps)
    with pytest.raises(ContractViolation, match="positive and finite"):
        check_path_system(bad)


def test_contract_broken_row_map(checks_on):
    top, comm, ps = _small_ps()
    cut = fail_links(top, n_links=2, seed=5)
    ps2 = update_path_system(ps, top, cut, comm)
    assert ps2.row_map is not None
    rm = ps2.row_map.copy()
    kept = np.flatnonzero(rm >= 0)
    assert kept.size >= 2, "delta must preserve some rows"
    rm[kept[1]] = rm[kept[0]]  # two rows claim one predecessor
    bad = dataclasses.replace(ps2, row_map=rm)
    with pytest.raises(ContractViolation, match="injectiv"):
        check_path_system(bad)


def test_contract_batch_finite_capacity_in_padded_slot(checks_on):
    systems = []
    for s in range(2):
        top = jellyfish(20 + 8 * s, 8, 4, seed=s)
        comm = random_permutation_traffic(top, seed=s + 7)
        systems.append(build_path_system(top, comm, k=4))
    batch = PathSystemBatch.from_systems(systems)
    pad = ~np.asarray(batch.slot_valid)
    assert pad.any(), "batch must have padded slots for this corruption"
    inv = batch.inv_cap.copy()
    i, s = np.argwhere(pad)[0]
    inv[i, s] = 0.5  # a finite capacity leaked into the padding
    bad = dataclasses.replace(batch, inv_cap=inv)
    with pytest.raises(ContractViolation, match="infinite capacity"):
        check_path_system_batch(bad)


def test_contract_batch_padded_row_owner(checks_on):
    systems = []
    for s in range(2):
        top = jellyfish(20 + 8 * s, 8, 4, seed=s)
        comm = random_permutation_traffic(top, seed=s + 7)
        systems.append(build_path_system(top, comm, k=4))
    batch = PathSystemBatch.from_systems(systems)
    n0 = int(batch.n_paths[0])
    assert n0 < batch.p_max, "instance 0 must have padded rows"
    owner = batch.path_owner.copy()
    owner[0, n0] = 0  # padded row stealing a real commodity
    bad = dataclasses.replace(batch, path_owner=owner)
    with pytest.raises(ContractViolation, match="padded row"):
        check_path_system_batch(bad)


def _layout_batch():
    systems = []
    for s in range(2):
        top = jellyfish(20 + 8 * s, 8, 4, seed=s)
        comm = random_permutation_traffic(top, seed=s + 7)
        systems.append(build_path_system(top, comm, k=4))
    return PathSystemBatch.from_systems(systems)


def test_contract_batch_segment_layout_holds(checks_on):
    """The canonical layout the split normalisation sums over holds for
    built batches, on every instance."""
    batch = _layout_batch()
    check_path_system_batch(batch, max_instances=0)
    check_segment_layout(batch)


@pytest.mark.parametrize("corrupt, match", [
    ("owner_decreases", "grouped by commodity"),
    ("dummy_inside", "must not belong to the dummy"),
    ("real_in_tail", "padded row"),
])
def test_contract_batch_segment_layout_fires(checks_on, corrupt, match):
    """A row layout that ``_seg_norm`` would sum wrongly is refused, past
    ``max_instances`` (no per-instance comparison) too."""
    batch = _layout_batch()
    owner = batch.path_owner.copy()
    n1 = int(batch.n_paths[1])
    check = functools.partial(check_path_system_batch, max_instances=0)
    if corrupt == "owner_decreases":
        first = int(np.argmax(owner[1] != owner[1, 0]))
        owner[1, [0, first]] = owner[1, [first, 0]]
        check = check_segment_layout
    elif corrupt == "dummy_inside":
        owner[1, n1 - 1] = batch.demands.shape[1] - 1
    else:
        owner[1, n1] = owner[1, n1 - 1]
    bad = dataclasses.replace(batch, path_owner=owner)
    with pytest.raises(ContractViolation, match=match):
        check(bad)


def test_contract_sim_result_fires(checks_on):
    top = jellyfish(24, 8, 4, seed=1)
    comm = random_permutation_traffic(top, seed=2)
    ps = build_path_system(top, comm, k=4)
    wl = steady_poisson(10, rate=3.0, size=8.0)
    cfg = SimConfig(max_flows=128, max_arrivals=4, wf_iters=4)
    res = simulate([ps], wl, policy="ecmp", config=cfg, seed=0)
    thr = np.asarray(res.throughput).copy()
    thr[0, 0] = -1.0
    bad = dataclasses.replace(res, throughput=thr)
    with pytest.raises(ContractViolation, match="throughput"):
        check_sim_state(bad)


def test_contracts_pass_on_real_builders(checks_on):
    # check_path_system runs INSIDE build_path_system when enabled; these
    # must construct without a ContractViolation across topology families
    tops = [
        jellyfish(30, 10, 6, seed=0),
        fattree(4),
        build_clos(ClosSpec(n_leaves=4, servers_per_leaf=4,
                            uplinks_per_leaf=4, n_spines=4, spine_ports=4)),
        swdc_ring(24, 8, seed=0, degree=4),
    ]
    for top in tops:
        comm = random_permutation_traffic(top, seed=1)
        ps = build_path_system(top, comm, k=4)
        check_path_system(ps, top, name=f"recheck[{top.name}]")


def test_contracts_pass_on_delta_chain(checks_on):
    # update_path_system validates its spliced output when enabled; a
    # fail + heal chain must stay contract-clean end to end
    top, comm, ps = _small_ps()
    cut = fail_links(top, n_links=2, seed=11)
    ps_cut = update_path_system(ps, top, cut, comm)
    ps_back = update_path_system(ps_cut, cut, top, comm)
    check_path_system(ps_back, top, name="delta-heal")


def test_argsort_regression_hashseed_independent():
    # Satellite of PR 6: the slot-lookup argsort at routing's enumerator
    # boundary was unstable (numpy introsort over equal keys).  The path
    # table must be byte-identical across Python hash seeds.
    prog = (
        "import hashlib, numpy as np\n"
        "from repro.core import build_path_system, jellyfish, "
        "random_permutation_traffic\n"
        "top = jellyfish(24, 8, 4, seed=3)\n"
        "comm = random_permutation_traffic(top, seed=4)\n"
        "ps = build_path_system(top, comm, k=4)\n"
        "h = hashlib.sha256()\n"
        "for a in (ps.path_edges, ps.path_len, ps.path_owner):\n"
        "    h.update(np.ascontiguousarray(a).tobytes())\n"
        "print(h.hexdigest())\n"
    )
    digests = []
    for seed in ("0", "424242"):
        r = subprocess.run(
            [sys.executable, "-c", prog],
            capture_output=True, text=True,
            env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
                 "PYTHONHASHSEED": seed},
        )
        assert r.returncode == 0, r.stderr
        digests.append(r.stdout.strip())
    assert digests[0] == digests[1]


# --------------------------------------------------------------------------- #
# retrace tracer
# --------------------------------------------------------------------------- #


def test_counter_sees_fresh_compiles():
    import jax
    import jax.numpy as jnp

    from repro.analysis.retrace import track_compiles

    with track_compiles() as c:
        fresh = jax.jit(lambda x: x * 2 + 1)
        fresh(jnp.arange(7.0)).block_until_ready()
    assert c.count >= 1
    assert all("backend_compile" in e for e in c.events)


def test_solver_recompiles_nothing_within_a_bucket():
    from repro.analysis.retrace import solver_cache_sizes, track_compiles
    from repro.core import mw_concurrent_flow_batch

    def batch_of(seeds):
        out = []
        for s in seeds:
            top = jellyfish(22 + 2 * (s % 2), 8, 4, seed=s)
            comm = random_permutation_traffic(top, seed=s + 5)
            out.append(build_path_system(top, comm, k=4))
        return out

    mw_concurrent_flow_batch(batch_of([0, 1]), iters=24)  # warm the bucket
    before = solver_cache_sizes()
    with track_compiles() as c:
        mw_concurrent_flow_batch(batch_of([2, 3]), iters=24)
    after = solver_cache_sizes()
    assert c.count == 0, f"retrace within a shape bucket: {c.events}"
    assert after == before
