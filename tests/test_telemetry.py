"""Unified telemetry layer: registry cells, spans, JSONL, reconciliation.

The fast ``-m telemetry`` CI lane.  Everything here uses the tiny 24x24
spec so the whole module compiles a handful of small executables once
(module-scoped serving fixture) and the rest is pure host-side checks.
"""

from __future__ import annotations

import json
import types

import jax
import numpy as np
import pytest

from repro import fpca
from repro.core import mapping
from repro.core.mapping import FPCASpec
from repro.fpca import telemetry
from repro.fpca.cache import ExecutableCache
from repro.fpca.telemetry import MetricFamily, OVERFLOW_LABEL
from repro.serving.fpca_pipeline import FPCAPipeline, PipelineStats
from repro.serving.observe import (
    assert_reconciled,
    fleet_report,
    render_fleet_report,
)
from repro.serving.streaming import StreamServer

pytestmark = pytest.mark.telemetry

SPEC = FPCASpec(image_h=24, image_w=24, out_channels=4, kernel=3, stride=2)


@pytest.fixture(autouse=True)
def _no_leaked_session():
    yield
    telemetry.disable()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One gated fleet served with telemetry on: per-tick ticks, then a
    compiled segment, then per-tick again (span nesting across modes)."""
    path = tmp_path_factory.mktemp("telemetry") / "events.jsonl"
    rng = np.random.default_rng(0)
    kernel = rng.normal(size=(4, 3, 3, 3)).astype(np.float32)
    pipe = FPCAPipeline(backend="basis")
    pipe.register("edges", SPEC, kernel)
    server = StreamServer(
        pipe,
        gate=fpca.DeltaGateConfig(threshold=0.05, keyframe_interval=6),
        controller=fpca.GateControllerConfig(target=0.5),
    )
    server.add_stream("cam0", "edges")
    frames = (rng.normal(size=(12, 24, 24, 3)) * 0.1).astype(np.float32)
    telemetry.enable(path)
    list(server.serve("cam0", frames[:4]))
    list(server.serve_segments("cam0", frames[4:8], segment_length=4))
    list(server.serve("cam0", frames[8:]))
    telemetry.disable()
    return types.SimpleNamespace(
        pipe=pipe, server=server, path=path,
        events=telemetry.read_jsonl(path),
    )


# -- JSONL export ------------------------------------------------------------


def test_jsonl_strict_roundtrip(served):
    """Every line is strict RFC 8259 JSON with ts/event keys; the session
    frames the log."""
    raw = served.path.read_text().strip().splitlines()
    assert len(raw) == len(served.events) > 2
    for line, ev in zip(raw, served.events):
        assert json.loads(line) == ev          # parse == parsed
        json.dumps(ev, allow_nan=False)        # strictly re-serialisable
        assert "Infinity" not in line and "NaN" not in line
        assert "ts" in ev and "event" in ev
    assert served.events[0]["event"] == "session_start"
    assert served.events[-1]["event"] == "session_end"


def test_span_nesting_across_segments(served):
    """run_segment spans nest under serve_segment; tick spans are roots."""
    spans = [e for e in served.events if e["event"] == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["span"], []).append(s)
    assert set(by_name) >= {"serve_tick", "serve_segment", "run_segment"}
    for s in by_name["run_segment"]:
        assert s["parent"] == "serve_segment"
        assert s["depth"] >= 1
    for s in by_name["serve_segment"] + by_name["serve_tick"]:
        assert s["parent"] is None
        assert s["dur_s"] >= 0


# -- per-tick phase spans and host<->device byte counters ----------------------

PHASES = ("stage", "gate", "frontend", "head")
N_CAMS, N_TICKS = 3, 6


def _serve_model_fleet(path=None):
    """Three gated cameras on a model config, fresh noise every tick (so
    every tick keeps windows): served with telemetry on when ``path`` is
    given, off otherwise."""
    rng = np.random.default_rng(2)
    kernel = rng.normal(size=(4, 3, 3, 3)).astype(np.float32)
    model = fpca.FPCAModelProgram(
        frontend=fpca.FPCAProgram(spec=SPEC),
        head=(fpca.DenseSpec(8, activation="relu"), fpca.DenseSpec(3)),
    )
    pipe = FPCAPipeline(backend="basis")
    pipe.register("cls", model, kernel,
                  head_params=model.init_head(jax.random.PRNGKey(0)))
    server = StreamServer(pipe, gate=fpca.DeltaGateConfig(threshold=0.05))
    ids = [f"cam{i}" for i in range(N_CAMS)]
    for sid in ids:
        server.add_stream(sid, "cls")
    ticks = [
        {sid: rng.uniform(0, 1, (24, 24, 3)).astype(np.float32) for sid in ids}
        for _ in range(N_TICKS)
    ]
    if path is not None:
        telemetry.enable(path)
    out = list(server.run(ticks))
    telemetry.disable()
    events = telemetry.read_jsonl(path) if path is not None else []
    return server, out, [e for e in events if e["event"] == "span"]


def _reckoned_bytes(server) -> tuple[int, int]:
    """Host->device and device->host bytes of N_TICKS ticks, from shapes."""
    h_o, w_o = mapping.output_dims(SPEC)
    eff = 4 * SPEC.eff_h * SPEC.eff_w                  # float32 effective frame
    frame = 4 * 24 * 24 * 3                            # float32 camera frame
    grid = 4 * (-(-SPEC.eff_h // SPEC.skip_block)) * (-(-SPEC.eff_w // SPEC.skip_block))
    keep = h_o * w_o                                   # bool window keep grid
    padded = 4                                         # pow-2 batch of 3
    assert server.sessions["cam0"]._prev.nbytes == eff
    # the frames go up once a tick and feed gate and frontend; previous
    # effective frames stay on the device, only the |Δ| grid comes back
    gate_d2h = N_TICKS * N_CAMS * grid                           # step_batch
    tick_h2d = N_TICKS * (N_CAMS * frame + padded * keep + N_CAMS * keep)
    tick_d2h = N_TICKS * N_CAMS * (4 * h_o * w_o * 4 + 4 * 3)   # counts, logits
    return tick_h2d, gate_d2h + tick_d2h


@pytest.fixture(scope="module")
def fleet_spans(tmp_path_factory):
    return _serve_model_fleet(tmp_path_factory.mktemp("phases") / "spans.jsonl")


def test_phase_spans_nest_under_serve_tick(fleet_spans):
    """stage, gate, frontend and head run once per tick, in that order,
    inside their serve_tick; every record carries t0_ns."""
    _, _, spans = fleet_spans
    ticks = [s for s in spans if s["span"] == "serve_tick"]
    assert [s["tick"] for s in ticks] == list(range(N_TICKS))
    for s in spans:
        assert isinstance(s["t0_ns"], int) and s["dur_s"] >= 0
    for tick in ticks:
        lo = tick["t0_ns"]
        hi = lo + int(tick["dur_s"] * 1e9) + 1
        inside = [s for s in spans if s["span"] in PHASES and lo <= s["t0_ns"] <= hi]
        assert [s["span"] for s in inside] == list(PHASES)
        for s in inside:
            assert s["parent"] == "serve_tick" and s["depth"] == 1
            assert s["t0_ns"] + int(s["dur_s"] * 1e9) <= hi


def test_gate_sub_spans_nest_under_gate(fleet_spans):
    """gate_readback (the batched gate dispatch and its read-back), then
    gate_streams (the host pass over the group's gate states), run once
    per tick inside that tick's gate span."""
    _, _, spans = fleet_spans
    gates = [s for s in spans if s["span"] == "gate"]
    assert len(gates) == N_TICKS
    for gate in gates:
        lo = gate["t0_ns"]
        hi = lo + int(gate["dur_s"] * 1e9) + 1
        inside = [s for s in spans if s["span"].startswith("gate_")
                  and lo <= s["t0_ns"] <= hi]
        assert [s["span"] for s in inside] == ["gate_readback", "gate_streams"]
        assert inside[0]["t0_ns"] + int(inside[0]["dur_s"] * 1e9) <= inside[1]["t0_ns"] + 1
        for s in inside:
            assert s["parent"] == "gate" and s["depth"] == 2
            assert s["t0_ns"] + int(s["dur_s"] * 1e9) <= hi


def test_realise_follows_its_tick_at_depth_two(fleet_spans):
    """One realise per tick with the tick's id, starting after that tick's
    serve_tick ends and after tick + depth was dispatched (the depth-2
    overlap telemetry must not change)."""
    server, out, spans = fleet_spans
    ends = {s["tick"]: s["t0_ns"] + int(s["dur_s"] * 1e9)
            for s in spans if s["span"] == "serve_tick"}
    realised = [s for s in spans if s["span"] == "realise"]
    assert [s["tick"] for s in realised] == list(range(N_TICKS))
    for r in realised:
        assert r["parent"] is None
        assert r["t0_ns"] >= ends[r["tick"]]
        later = r["tick"] + server.depth
        if later in ends:
            assert r["t0_ns"] >= ends[later]
    assert [res[0].frame_idx for res in out] == list(range(N_TICKS))


@pytest.mark.parametrize("on", [True, False], ids=["enabled", "disabled"])
def test_byte_counters_match_shapes(on, fleet_spans):
    """h2d_bytes / d2h_bytes are the nbytes reckoned from the shapes, with
    telemetry on or off: disabled, every span is the shared null object and
    the counters still count."""
    if on:
        server = fleet_spans[0]
    else:
        server = _serve_model_fleet()[0]
        for name in PHASES + ("serve_tick", "realise", "gate_readback",
                              "gate_streams"):
            assert telemetry.span(name) is telemetry._NULL_SPAN
    assert (server.stats.h2d_bytes, server.stats.d2h_bytes) == _reckoned_bytes(server)
    assert_reconciled(server.pipeline, server)


@pytest.mark.parametrize("profile", [True, False])
def test_span_enters_profiler_annotation(profile, monkeypatch):
    """With profile=True a span runs inside TraceAnnotation("fpca:<name>");
    with profile=False it creates none."""
    entered = []

    class Annotation:
        def __init__(self, name, **kw):
            assert not kw
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    telemetry.enable(None, profile=profile)
    with telemetry.span("gate", {"tick": 3}):
        with telemetry.span("stage"):
            pass
    telemetry.disable()
    if profile:
        assert entered == [("enter", "fpca:gate"), ("enter", "fpca:stage"),
                           ("exit", "fpca:stage"), ("exit", "fpca:gate")]
    else:
        assert entered == []


# -- reconciliation / single-sourcing ----------------------------------------


def test_stats_surfaces_reconcile_exactly(served):
    assert_reconciled(served.pipe, served.server)


def test_fleet_report_matches_legacy_counters(served):
    rep = fleet_report(served.server)
    s = served.server.stats
    fleet = rep["fleet"]
    assert fleet["frames"] == s.frames == 12
    assert fleet["windows_total"] == s.windows_total
    assert fleet["windows_kept"] == s.windows_kept
    assert fleet["segments"] == s.segments == 1
    assert fleet["segment_ticks"] == s.segment_ticks == 4
    assert fleet["serve_seconds"] == s.serve_seconds > 0
    info = served.pipe.cache_info()
    assert fleet["cache"]["hits"] == info.hits
    assert fleet["cache"]["misses"] == info.misses
    json.dumps(rep, allow_nan=False)           # strict-JSON-able
    table = render_fleet_report(rep)
    assert "cam0" in table and "edges" in table


def test_no_double_counting(served):
    """The old bug: windows_executed mirrored into the pipeline AND the
    handle.  Parent-chained cells make the pipeline total exactly the sum
    of its handles' cells — no more, no less."""
    handles = list(served.pipe._handles.values())
    assert handles
    total = sum(h.stats.windows_executed for h in handles)
    assert served.pipe.stats.windows_executed == total
    total_skip = sum(h.stats.launches_skipped for h in handles)
    assert served.pipe.stats.launches_skipped == total_skip


def test_servo_telemetry_gauges(served):
    text = telemetry.registry().render()
    assert 'fpca_gate_threshold{controller="cam0/edges"}' in text
    ctl = served.server.sessions["cam0"].controller
    fam = telemetry.registry().gauge("fpca_gate_threshold")
    assert ctl.threshold == fam.labels(controller="cam0/edges").value


def test_fleet_allocation_gauges_sum_to_budget_and_reconcile():
    """The fleet arbiter's per-tenant rollups: allocation gauges sum to the
    global budget gauge, and admission rejections leave every stats surface
    exactly reconciled (a rejected stream must not touch serving counters)."""
    from repro.serving.fleet import (
        FleetAdmissionError,
        FleetConfig,
        FleetController,
    )

    rng = np.random.default_rng(1)
    kernel = rng.normal(size=(4, 3, 3, 3)).astype(np.float32)
    pipe = FPCAPipeline(backend="basis")
    pipe.register("edges", SPEC, kernel)
    server = StreamServer(
        pipe,
        gate=fpca.DeltaGateConfig(threshold=0.05, keyframe_interval=6),
        controller=fpca.GateControllerConfig(target=0.5),
    )
    fc = FleetController(server, FleetConfig(budget=0.6, floor=0.2))
    fc.add_stream("t0", "edges")
    fc.add_stream("t1", "edges", priority=2.0)
    fc.add_stream("t2", "edges")
    with pytest.raises(FleetAdmissionError):        # capacity = 3
        fc.add_stream("t3", "edges")
    reg = telemetry.registry()
    alloc = {
        labels["stream"]: value
        for name, _k, labels, value in reg.collect()
        if name == "fpca_fleet_allocation"
        and labels.get("stream") in ("t0", "t1", "t2")
    }
    budget = [v for n, _k, _l, v in reg.collect() if n == "fpca_fleet_budget"]
    assert sum(alloc.values()) == pytest.approx(budget[0]) == 0.6
    # the rendered export carries the same cells
    text = reg.render()
    assert 'fpca_fleet_allocation{stream="t1"}' in text
    assert "fpca_fleet_rejected_total" in text
    # rejected admission left serving telemetry untouched and reconciled
    assert len(server.sessions) == 3
    assert_reconciled(pipe, server)
    json.dumps(fc.arbitration_table(), allow_nan=False)


# -- StatsView semantics -----------------------------------------------------


def test_parent_chain_and_parent_map():
    parent = PipelineStats()
    child = fpca.FrontendStats(parent=parent)
    child.runs += 2
    child.windows_executed += 5
    child.reprograms += 1
    assert parent.batches == 2                 # _PARENT_MAP runs -> batches
    assert parent.windows_executed == 5
    assert child.snapshot()[0] == 2
    with pytest.raises(AttributeError):
        child.not_a_field
    with pytest.raises(AttributeError):
        child.not_a_field = 1
    d = child.as_dict()
    assert d["runs"] == 2 and d["reprograms"] == 1


def test_registry_export_tracks_views_live():
    view = fpca.FrontendStats()
    view.windows_total += 7
    inst = view._labels["instance"]
    rows = {
        (n, l.get("instance")): v
        for n, _k, l, v in telemetry.registry().collect()
    }
    assert rows[("fpca_frontend_windows_total", inst)] == 7


# -- registry ----------------------------------------------------------------


def test_label_cardinality_bounded():
    fam = MetricFamily("test_bounded_total", "counter", "", ("stream",),
                       max_label_sets=4)
    for i in range(10):
        fam.labels(stream=f"s{i}").add(1)
    # 4 interned + 1 shared overflow cell, never more
    assert len(fam._cells) == 5
    assert fam.overflowed == 6
    overflow = fam.labels(stream="anything_new")
    assert overflow is fam._cells[(OVERFLOW_LABEL,)]
    total = sum(c.value for c in fam._cells.values())
    assert total == 10                          # totals stay honest


def test_prometheus_render_shape():
    reg = telemetry.registry()
    reg.histogram("test_render_seconds", "help text", ("site",)).labels(
        site="x").observe(0.002)
    text = reg.render()
    assert "# TYPE test_render_seconds histogram" in text
    assert "# HELP test_render_seconds help text" in text
    assert 'test_render_seconds_bucket{site="x",le="+Inf"} 1' in text
    assert 'test_render_seconds_count{site="x"} 1' in text
    cnt = reg.counter("test_render_total")
    cnt.cell().add(3)
    assert "test_render_total 3" in reg.render()


def test_snapshot_is_strict_json():
    snap = telemetry.registry().snapshot()
    json.dumps(snap, allow_nan=False)


# -- disabled mode -----------------------------------------------------------


def test_disabled_mode_allocates_nothing():
    telemetry.disable()
    assert not telemetry.enabled()
    # the null span is ONE shared object: no per-call allocation at all
    s1, s2 = telemetry.span("serve_tick"), telemetry.span("compile")
    assert s1 is s2 is telemetry._NULL_SPAN
    fields = {"stream": "cam0"}
    assert telemetry.span("serve_tick", fields) is s1
    # events are dropped without touching any session state
    telemetry.event("servo_actuate", err=1.0)


def test_disabled_instrumented_launch_is_passthrough():
    calls = []

    def fn(x):
        calls.append(x)
        return x * 2

    telemetry.disable()
    wrapped = telemetry.instrument_launch(fn, site="test", backend="ref")
    fam = telemetry.registry().counter("fpca_launches_total")
    cell = fam.labels(site="test", backend="ref")
    before = cell.value
    assert wrapped(21) == 42
    assert cell.value == before                # nothing counted when off
    telemetry.enable(None)
    assert wrapped(1) == 2
    assert cell.value == before + 1            # counted when on
    telemetry.disable()
    assert wrapped.__wrapped__ is fn


# -- executable cache --------------------------------------------------------


def test_cache_eviction_ordering_and_verbose_info():
    cache = ExecutableCache(capacity=2)
    cache.get(("a",), lambda: "A")
    cache.get(("b",), lambda: "B")
    cache.get(("a",), lambda: "A")             # refresh a: b is now LRU
    cache.get(("c",), lambda: "C")             # evicts b
    cache.get(("d",), lambda: "D")             # evicts a
    info = cache.info(verbose=True)
    assert info.eviction_log == (("b",), ("a",))
    assert info.resident == (("c",), ("d",))   # LRU-first ordering
    assert info.by_key[("a",)] == (1, 1)       # 1 hit, 1 miss
    assert info.by_key[("b",)] == (0, 1)
    assert (info.hits, info.misses, info.evictions) == (1, 4, 2)
    # non-verbose stays the stable 5-tuple the API contract pins
    assert cache.info() == (1, 4, 2, 2, 2)


def test_eviction_log_is_bounded():
    cache = ExecutableCache(capacity=1)
    cache.eviction_log_cap  # class attr exists
    for i in range(cache.eviction_log_cap + 10):
        cache.get((i,), lambda: i)
    log = cache.info(verbose=True).eviction_log
    assert len(log) == cache.eviction_log_cap
    assert log[-1] == (cache.eviction_log_cap + 8,)   # newest retained
