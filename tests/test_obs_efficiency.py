"""Tier-1 tests for the efficiency-and-postmortem layer: goodput
accounting (bucket classification, fractions partitioning wall-clock,
the straggler exchange), histogram quantile/SLO estimation, perf
helpers (device specs, cost-model FLOPs, MFU, token counting), the
crash flight recorder (ring, dumps, excepthook), the chaos-injected
crash -> valid post-mortem path, and the catalog contract lint."""
import ast
import glob
import json
import os
import sys
import threading

import pytest

import paddle_tpu  # noqa: F401  (forces the CPU/virtual-device conftest setup)
import paddle_tpu.observability as obs
from paddle_tpu.framework.flags import get_flag, set_flags
from paddle_tpu.observability import exposition, flight_recorder, goodput, perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def obs_on():
    """Enabled observability over zeroed registry/tracer/goodput/ring;
    restores the default-off state afterwards."""
    obs.get_registry().reset()
    obs.get_tracer().clear()
    goodput.get_tracker().reset()
    flight_recorder.get_recorder().clear()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        set_flags({"obs_postmortem_dir": ""})
        obs.get_registry().reset()
        obs.get_tracer().clear()
        goodput.get_tracker().reset()
        flight_recorder.get_recorder().clear()


# -- goodput tracker --------------------------------------------------------
def test_goodput_fractions_partition_wall_clock(obs_on):
    tr = goodput.GoodputTracker()
    tr.start()
    tr.account("productive_step", 0.6)
    tr.account("compile", 0.2)
    tr.account("checkpoint_save", 0.1)
    rep = tr.report()
    assert abs(sum(rep["fractions"].values()) - 1.0) < 1e-9
    # wall-clock barely advanced, so accounted (0.9s) dominates total
    assert rep["total_seconds"] == pytest.approx(0.9, rel=1e-6)
    assert rep["fractions"]["productive_step"] == pytest.approx(2 / 3)
    assert rep["goodput_ratio"] == pytest.approx(2 / 3)
    assert rep["badput_seconds"] == pytest.approx(0.3)


def test_goodput_idle_fills_unaccounted_wall_clock(obs_on):
    import time

    tr = goodput.GoodputTracker()
    tr.start()
    time.sleep(0.05)
    tr.account("productive_step", 0.01)
    rep = tr.report()
    assert rep["seconds"]["idle"] > 0
    assert abs(sum(rep["fractions"].values()) - 1.0) < 1e-9
    assert rep["wall_seconds"] >= 0.05


def test_goodput_unknown_bucket_rejected(obs_on):
    with pytest.raises(ValueError):
        goodput.get_tracker().account("coffee_break", 1.0)


def test_goodput_account_noop_when_disabled():
    tr = goodput.GoodputTracker()
    tr.account("productive_step", 5.0)     # obs off: must not record
    assert tr.report()["seconds"]["productive_step"] == 0.0


def test_goodput_section_times_body(obs_on):
    import time

    tr = goodput.GoodputTracker()
    tr.start()
    with goodput.goodput_section("checkpoint_save", tr):
        time.sleep(0.02)
    assert tr.report()["seconds"]["checkpoint_save"] >= 0.015


def test_goodput_counter_accumulates_by_bucket(obs_on):
    goodput.account("data_wait", 0.25)
    goodput.account("data_wait", 0.25)
    c = obs.get_registry().counter("goodput_time_seconds_total")
    assert c.labels(bucket="data_wait").value == pytest.approx(0.5)


# -- straggler exchange -----------------------------------------------------
class _FakeStore:
    """Duck-typed set/wait pair backing TCPStore.gather's contract."""

    def __init__(self):
        self._kv = {}
        self._cv = threading.Condition()

    def set(self, key, value):
        with self._cv:
            self._kv[key] = value if isinstance(value, bytes) \
                else str(value).encode()
            self._cv.notify_all()

    def wait(self, key):
        with self._cv:
            while key not in self._kv:
                self._cv.wait(timeout=5)
            return self._kv[key]


def test_exchange_step_times_flags_stragglers(obs_on):
    from paddle_tpu.distributed.store import TCPStore

    store = _FakeStore()
    store.gather = TCPStore.gather.__get__(store)   # reuse the real logic
    # three "hosts" publish; rank 2 is 10x the median
    for r, t in ((1, 1.0), (2, 10.0)):
        store.set(f"goodput/steptime/7/{r}", repr(t))
    times, stragglers = goodput.exchange_step_times(
        store, rank=0, world_size=3, step_seconds=1.1, round_id=7)
    assert times == [1.1, 1.0, 10.0]
    assert stragglers == [2]
    c = obs.get_registry().counter("goodput_stragglers_total")
    assert c.labels().value == 1
    evs = [e for e in flight_recorder.get_recorder().events()
           if e["kind"] == "straggler"]
    assert evs and evs[-1]["ranks"] == [2]


def test_exchange_no_stragglers_under_factor(obs_on):
    from paddle_tpu.distributed.store import TCPStore

    store = _FakeStore()
    store.gather = TCPStore.gather.__get__(store)
    store.set("goodput/steptime/0/1", repr(1.2))
    _times, stragglers = goodput.exchange_step_times(
        store, rank=0, world_size=2, step_seconds=1.0, round_id=0)
    assert stragglers == []


# -- histogram quantiles / SLO readout --------------------------------------
def test_quantile_log_interpolation():
    bounds = [1.0, 10.0, 100.0]
    counts = [0, 100, 0, 0]          # all mass in (1, 10]
    q50 = exposition.quantile(bounds, counts, 0.5)
    # log-midpoint of (1, 10] is sqrt(10), not the linear 5.5
    assert q50 == pytest.approx(10 ** 0.5, rel=1e-6)
    assert exposition.quantile(bounds, counts, 1.0) == pytest.approx(10.0)


def test_quantile_empty_and_inf_bucket():
    bounds = [1.0, 10.0]
    assert exposition.quantile(bounds, [0, 0, 0], 0.5) is None
    # mass beyond the largest finite bound clamps to it
    assert exposition.quantile(bounds, [0, 0, 5], 0.99) == 10.0


def test_fraction_at_or_below():
    bounds = [1.0, 10.0, 100.0]
    counts = [50, 50, 0, 0]
    f = exposition.fraction_at_or_below
    assert f(bounds, counts, 100.0) == pytest.approx(1.0)
    assert f(bounds, counts, 1.0) == pytest.approx(0.5)
    # log-midpoint of (1, 10]: half that bucket's mass counted
    assert f(bounds, counts, 10 ** 0.5) == pytest.approx(0.75, rel=1e-6)
    assert f(bounds, [0, 0, 0, 0], 1.0) is None


def test_snapshot_rows_include_percentiles(obs_on):
    h = obs.histogram("t_quant_seconds")
    for v in (0.01, 0.02, 0.05, 0.1, 1.0):
        h.observe(v)
    rows = exposition.snapshot_rows(exposition.snapshot())
    row = next(r for r in rows if r[0] == "t_quant_seconds")
    assert "p50=" in row[3] and "p95=" in row[3] and "p99=" in row[3]


def test_slo_attainment_from_histogram(obs_on):
    h = obs.histogram("t_slo_seconds")
    for v in (0.01, 0.02, 5.0, 9.0):
        h.observe(v)
    a = perf.slo_attainment(h, 1.0)
    assert 0.4 <= a <= 0.6            # 2 of 4 under the 1s target


def test_snapshot_rows_show_zero_gauge_when_set(obs_on):
    # 0% SLO attainment must surface in the table; never-set gauges stay
    # hidden (every instrument mints a labelless series at import).
    g_set = obs.gauge("t_zero_set_gauge")
    g_set.set(0.0)
    obs.gauge("t_never_set_gauge")
    rows = exposition.snapshot_rows(exposition.snapshot())
    names = [r[0] for r in rows]
    assert "t_zero_set_gauge" in names
    assert "t_never_set_gauge" not in names


def test_update_serving_slo_gauges(obs_on):
    reg = obs.get_registry()
    ttft = reg.histogram("serving_ttft_seconds")
    tpot = reg.histogram("serving_tpot_seconds")
    for v in (0.1, 0.2, 3.0):
        ttft.observe(v)
    for v in (0.01, 0.9):
        tpot.observe(v)
    set_flags({"obs_slo_ttft_ms": 1000.0, "obs_slo_tpot_ms": 250.0})
    perf.update_serving_slo_gauges(ttft, tpot)
    g1 = reg.gauge("serving_slo_ttft_attainment").labels().value
    g2 = reg.gauge("serving_slo_tpot_attainment").labels().value
    assert 0.5 < g1 < 0.8             # 2 of 3 TTFTs under 1s
    assert 0.3 < g2 < 0.7             # 1 of 2 TPOTs under 250ms


# -- perf helpers -----------------------------------------------------------
class _Dev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


def test_device_specs_lookup():
    assert perf.peak_flops(_Dev("TPU v5e")) == 197e12
    assert perf.peak_flops(_Dev("TPU v5 lite")) == 197e12
    assert perf.hbm_bytes(_Dev("TPU v4")) == 32e9
    assert perf.hbm_bandwidth(_Dev("tpu v5p")) == 2.77e12
    # a kind the table does not list is an error, never another
    # chip's peak — the CPU included
    for unknown in (_Dev("cpu", platform="cpu"), _Dev("TPU v9000")):
        for fn in (perf.peak_flops, perf.hbm_bytes, perf.hbm_bandwidth):
            with pytest.raises(ValueError, match="DEVICE_SPECS"):
                fn(unknown)


def test_mfu_math():
    dev = _Dev("TPU v5e")
    # half the peak for one second = 50% MFU
    assert perf.mfu(197e12 / 2, 1.0, dev) == pytest.approx(0.5)
    assert perf.mfu(None, 1.0, dev) is None
    assert perf.mfu(1e12, 0.0, dev) is None
    # no peak is defined for the CPU: MFU is undefined there
    assert perf.mfu(1e12, 1.0, _Dev("cpu", platform="cpu")) is None


def test_flops_of_jitted_matmul():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((32, 32), jnp.float32)
    f = perf.flops_of(jax.jit(lambda a, b: a @ b), x, x)
    if f is None:
        pytest.skip("backend offers no cost analysis")
    assert f == pytest.approx(2 * 32 ** 3, rel=0.5)


def test_flops_of_untraceable_returns_none():
    assert perf.flops_of(lambda a: sorted(a), [3, 1]) is None


def test_token_count_integer_leaves_only():
    import numpy as np

    batch = {"ids": np.zeros((4, 128), np.int32),
             "mask": np.zeros((4, 128), np.float32)}
    assert perf.token_count(batch) == 4 * 128
    assert perf.token_count(np.zeros((3,), np.float32)) == 0
    # nested pytrees flatten fully (a one-level walk silently returns 0)
    nested = {"inputs": {"input_ids": np.zeros((2, 8), np.int32)},
              "extra": [np.zeros((5,), np.int64), 3.0]}
    assert perf.token_count(nested) == 2 * 8 + 5


# -- flight recorder --------------------------------------------------------
def test_ring_bounded_and_ordered(obs_on):
    fr = flight_recorder.FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("step", step=i)
    evs = fr.events()
    assert [e["step"] for e in evs] == [6, 7, 8, 9]


def test_record_noop_when_disabled():
    fr = flight_recorder.FlightRecorder(capacity=4)
    fr.record("step", step=1)
    assert fr.events() == []


def test_flag_changes_land_in_ring(obs_on):
    set_flags({"obs_slo_ttft_ms": 123.0})
    def flips():
        return [e for e in flight_recorder.get_recorder().events()
                if e["kind"] == "flag_change"
                and e["flag"] == "obs_slo_ttft_ms"]
    assert len(flips()) == 1
    # an idempotent re-set is NOT incident evidence: it must not evict
    # real events from the bounded ring
    set_flags({"obs_slo_ttft_ms": 123.0})
    assert len(flips()) == 1
    set_flags({"obs_slo_ttft_ms": 1000.0})
    assert len(flips()) == 2


def test_capacity_flag_resizes_live_ring(obs_on):
    old = int(get_flag("obs_flight_capacity"))
    try:
        set_flags({"obs_flight_capacity": 3})
        for i in range(6):
            flight_recorder.record("step", step=i)
        assert len(flight_recorder.get_recorder().events()) <= 3
    finally:
        set_flags({"obs_flight_capacity": old})


def test_dump_writes_valid_postmortem(tmp_path, obs_on):
    with obs.trace_span("outer"):
        flight_recorder.record("step", step=3)
        path = flight_recorder.dump(str(tmp_path / "pm.json"),
                                    trigger="manual")
    doc = json.load(open(path))
    assert doc["trigger"] == "manual"
    assert any(e["kind"] == "step" for e in doc["events"])
    assert any("outer" in names for names in doc["open_spans"].values())
    assert "metrics" in doc and "goodput" in doc
    c = obs.get_registry().counter("flight_recorder_dumps_total")
    assert c.labels(trigger="manual").value == 1


def test_maybe_dump_requires_dir_flag(tmp_path, obs_on):
    assert flight_recorder.maybe_dump("exception") is None  # no dir set
    set_flags({"obs_postmortem_dir": str(tmp_path)})
    p = flight_recorder.maybe_dump("exception")
    assert p is not None and os.path.exists(p)
    assert json.load(open(p))["trigger"] == "exception"


def test_excepthook_install_uninstall(tmp_path, obs_on):
    set_flags({"obs_postmortem_dir": str(tmp_path)})
    orig = sys.excepthook
    flight_recorder.install()
    try:
        assert sys.excepthook is not orig
        sys.excepthook(ValueError, ValueError("boom"), None)
        dumps = glob.glob(str(tmp_path / "postmortem-*.json"))
        assert dumps
        doc = json.load(open(dumps[0]))
        assert doc["error"]["type"] == "ValueError"
    finally:
        flight_recorder.uninstall()
    assert sys.excepthook is orig


# -- the chaos path: injected crash -> post-mortem + goodput report ---------
def _loop(tmp_path, injector, n=16):
    import jax.numpy as jnp

    from paddle_tpu.distributed.resilience import ResilientTrainLoop

    def step_fn(state, batch):
        w = state["w"] - 0.1 * batch.mean()
        return {"w": w}, jnp.abs(w).sum()

    batches = [jnp.full((2,), 0.1 * (i + 1)) for i in range(n)]
    return ResilientTrainLoop(
        step_fn, {"w": jnp.ones((2,))}, batches,
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=3, rng_key=None,
        injector=injector)


def test_chaos_crash_writes_postmortem_and_goodput(tmp_path, obs_on):
    from paddle_tpu.distributed.resilience import (FaultInjector,
                                                   SimulatedCrash)

    set_flags({"obs_postmortem_dir": str(tmp_path / "pm")})
    loop = _loop(tmp_path, FaultInjector("nan_grad@2, crash@5"))
    with pytest.raises(SimulatedCrash):
        loop.run(16)

    dumps = glob.glob(str(tmp_path / "pm" / "postmortem-*.json"))
    assert len(dumps) == 1, "crash must write exactly one post-mortem"
    doc = json.load(open(dumps[0]))
    assert doc["trigger"] == "exception"
    assert doc["error"]["type"] == "SimulatedCrash"
    kinds = [e["kind"] for e in doc["events"]]
    assert "rollback" in kinds and "exception" in kinds
    assert "step" in kinds and "checkpoint" in kinds

    gp = doc["goodput"]
    assert abs(sum(gp["fractions"].values()) - 1.0) < 0.01
    # the rolled-back NaN attempt is rollback-retry badput, never goodput
    assert gp["seconds"]["rollback_retry"] > 0
    assert gp["seconds"]["checkpoint_save"] > 0
    assert gp["goodput_ratio"] > 0


def test_train_loop_efficiency_gauges(tmp_path, obs_on):
    import numpy as np

    import jax.numpy as jnp

    from paddle_tpu.distributed.resilience import ResilientTrainLoop

    def step_fn(state, batch):
        w = state["w"] - 0.001 * batch["ids"].sum()
        return {"w": w}, jnp.abs(w).sum()

    batches = [{"ids": np.full((2, 8), i + 1, np.int32)} for i in range(4)]
    loop = ResilientTrainLoop(step_fn, {"w": jnp.ones(())}, batches,
                              rng_key=None)
    loop.run(4)
    reg = obs.get_registry()
    # the step's FLOPs come from the lowered program; on the CPU they
    # divide by no peak, so the MFU gauge stays unset
    assert loop._flops and loop._flops > 0
    assert reg.gauge("train_mfu").labels().value == 0
    # 2x8 int32 ids per batch -> 16 tokens
    assert loop.tokens_per_batch == 16
    assert reg.gauge("train_tokens_per_second").labels().value > 0
    rep = goodput.get_tracker().report()
    assert rep["seconds"]["productive_step"] > 0


def test_sigterm_path_dumps_postmortem(tmp_path, obs_on):
    from paddle_tpu.distributed.resilience import FaultInjector

    set_flags({"obs_postmortem_dir": str(tmp_path / "pm")})
    loop = _loop(tmp_path, None, n=8)
    done = 0

    def on_event(ev):
        nonlocal done
        if ev["kind"] == "checkpoint_saved" and not done:
            done = 1
            loop._sigterm = True       # what the signal handler sets
    loop.on_event = on_event
    loop.run(8)
    dumps = glob.glob(str(tmp_path / "pm" / "postmortem-*.json"))
    assert dumps and json.load(open(dumps[0]))["trigger"] == "sigterm"


# -- catalog contract lint --------------------------------------------------
def _metric_name_literals():
    """Every string literal passed to counter(/gauge(/histogram(/
    instrument( CALLS under paddle_tpu/ (AST-level: docstring examples
    don't count)."""
    names = {}
    pkg = os.path.join(REPO, "paddle_tpu")
    for root, _dirs, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                fun = node.func
                attr = fun.attr if isinstance(fun, ast.Attribute) \
                    else fun.id if isinstance(fun, ast.Name) else None
                # modules import `instrument as _instrument`
                if attr is None or attr.lstrip("_") not in (
                        "counter", "gauge", "histogram", "instrument"):
                    continue
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    names.setdefault(node.args[0].value, path)
    return names


def test_catalog_contract_no_unregistered_or_dead_metrics():
    from paddle_tpu.observability.catalog import CATALOG

    used = _metric_name_literals()
    unregistered = {n: p for n, p in used.items() if n not in CATALOG}
    assert not unregistered, (
        "metric name literals missing from observability/catalog.py "
        f"(register them there): {unregistered}")
    dead = set(CATALOG) - set(used)
    assert not dead, (
        "catalog rows no source file instruments (delete them or wire "
        f"them up): {sorted(dead)}")


# -- tooling smoke ----------------------------------------------------------
def test_obs_dump_goodput_demo_and_postmortem_cli(tmp_path):
    """tools/obs_dump.py --demo goodput prints the bucket report and
    writes a post-mortem that --postmortem pretty-prints (subprocess:
    the demo's global obs.enable() must not leak into this session)."""
    import subprocess

    tool = os.path.join(REPO, "tools", "obs_dump.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, tool, "--demo", "goodput",
         "--out", str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=240,
        cwd=REPO, env=env)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out[-2000:]
    assert "goodput ratio" in out and "rollback_retry" in out
    pm = tmp_path / "postmortem.json"
    assert pm.exists()

    proc = subprocess.run(
        [sys.executable, tool, "--postmortem", str(pm)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=120,
        cwd=REPO, env=env)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out[-2000:]
    assert "trigger=manual" in out
    assert "rollback" in out              # the event tail names the NaN
    assert "goodput ratio" in out

    # re-running into the SAME --out must not resume from the previous
    # run's checkpoint (a stale-ckpt resume skips the whole workload and
    # reports 0 rollbacks / goodput 0)
    proc = subprocess.run(
        [sys.executable, tool, "--demo", "goodput",
         "--out", str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=240,
        cwd=REPO, env=env)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out[-2000:]
    assert "1 rollback(s)" in out and "rollback_retry" in out


def test_catalog_rows_instantiate(obs_on):
    """Every catalogued name must build its instrument (kind/bucket
    overrides consistent)."""
    from paddle_tpu.observability.catalog import CATALOG, instrument

    for name in CATALOG:
        instrument(name)
