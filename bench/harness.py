"""One benchmark cell, driven by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json`` with its plain
head reference ``<config>.py`` beside it) and a traffic mix
(``bench/traffic/<mix>.json``); its per-layer metrics are readers found by
their base name (``bench/metrics/<base>.py``, the part before the first
``.``); the limits its comparison holds are ``bench/limits/<workload>.json``.
Nothing here names a cell, a configuration or a mix: a new one is new files
and new entries in ``BENCHMARK.json``.

A run: build the model through the zoo, make its weights on the device from
the seed, fit the sensor model, build an ``FPCAPipeline`` and a
``StreamServer`` with one stream per camera, render the fleet's frames, serve
the traffic until no new program compiles (set-up), then serve for
``seconds`` (the window), then check a sample of what the window served
against the plain reference (``bench/reference.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import resource
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
WARM_CHUNKS_MAX = 20
WARM_TICKS_MAX = 200
LATENCY_QUANTILES = (50, 90, 95, 98, 99)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    cfg: dict
    head: object            # the configuration's head reference module
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    limits: dict


def load_cell(name: str, overrides: dict | None = None, entry: dict | None = None) -> Cell:
    """The cell named ``name`` in ``BENCHMARK.json``, or described by
    ``entry`` (a workload entry of the same form, for a cell not yet in the
    benchmark); ``overrides`` (``{"spec": {...}, "traffic": {...}}``)
    shrink it for a rehearsal off the chip."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = entry or next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in bench["configs"] if c["name"] == wl["config"]), None)
    cfg_path = (ROOT / conf["file"] if conf
                else BENCH / "configs" / f"{wl['config']}.json")
    cfg = json.loads(cfg_path.read_text())
    traffic = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json").read_text())
    overrides = overrides or {}
    cfg["spec"].update(overrides.get("spec", {}))
    traffic.update(overrides.get("traffic", {}))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, cfg=cfg, head=load_module(cfg_path.with_suffix(".py")),
        traffic=traffic, chips=int(wl["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
    )


class CompileCounter:
    """Counts traces and backend compiles the process makes (every new
    program shape traces; a miss of the persistent cache also compiles)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax._src.monitoring as monitoring

        self.n = 0
        self._monitoring = monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._on)


def seed_key(seed: int):
    import jax

    state = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(state) & 0x7FFFFFFF)


def make_weights(cell: Cell, seed: int) -> dict:
    """Kernel, BN offsets and head parameters, on the device, in one jitted
    call from the seed, in float32 as they are served."""
    import jax
    import jax.numpy as jnp

    cfg, w = cell.cfg, cell.cfg["weights"]
    s = cfg["spec"]
    shape = (s["out_channels"], s["kernel"], s["kernel"], s["in_channels"])

    @jax.jit
    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        kernel = jax.random.normal(k1, shape, jnp.float32) * w["kernel_std"]
        bn = jax.random.randint(k2, (shape[0],), 0, w["bn_offset_max"]).astype(jnp.float32)
        return {"kernel": kernel, "bn": bn, "head": cell.head.make_head_params(k3, cfg)}

    return jax.block_until_ready(make(seed_key(seed)))


def build_server(cell: Cell, weights: dict, ids: list):
    """The system under test: model program, pipeline and stream server."""
    from repro.core.adc import ADCConfig
    from repro.core.curvefit import fit_bucket_model
    from repro.core.device_models import CircuitParams
    from repro.core.fpca_sim import WeightEncoding
    from repro.core.mapping import FPCASpec
    from repro.fpca import DeltaGateConfig, zoo
    from repro.launch.mesh import make_host_mesh
    from repro.serving.fpca_pipeline import FPCAPipeline
    from repro.serving.streaming import StreamServer

    cfg, f = cell.cfg, cell.cfg["curvefit"]
    circuit = CircuitParams(**cfg["circuit"])
    model = zoo.build_model(
        cell.head.zoo_cfg(cfg), spec=FPCASpec(**cfg["spec"]),
        frontend={"circuit": circuit, "adc": ADCConfig(**cfg["adc"]),
                  "enc": WeightEncoding(**cfg["enc"])})
    bucket = fit_bucket_model(
        circuit, n_pixels=model.spec.n_active_pixels, n_buckets=f["n_buckets"],
        n_sweep=f["n_sweep"], degree_avg=f["degree_avg"],
        degree_buc=f["degree_buc"], grid=f["grid"])
    bucket = dataclasses.replace(bucket, sharpness=float(f["sharpness"]))
    pipe = FPCAPipeline(bucket, mesh=make_host_mesh(data=cell.chips) if cell.chips > 1 else None)
    pipe.register("model", model, weights["kernel"], weights["bn"],
                  head_params=weights["head"])
    server = StreamServer(pipe, DeltaGateConfig(**cfg["gate"]))
    for sid in ids:
        server.add_stream(sid, "model")
    return pipe, server


class Recorder:
    """What the check needs of the sampled cameras: every block keep grid
    they were served with, the effective count map their served counts
    build (each window holds the counts of the last tick that kept it), and
    a reservoir of their window ticks drawn from the seed (the last window
    tick always among them)."""

    def __init__(self, fleet, seed: int, check: dict, cfg: dict):
        from bench import reference

        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
        n = min(int(check["cameras"]), fleet.cameras)
        self.cams = {fleet.ids[i]: int(i) for i in sorted(rng.choice(fleet.cameras, n,
                                                                      replace=False))}
        self.k = max(int(check["ticks"]) - 1, 1)
        self.rng = rng
        self.cfg = cfg
        self.window_keep = reference.window_keep
        self.blocks = {sid: [] for sid in self.cams}
        self.eff = {sid: None for sid in self.cams}
        self.pool = {sid: {} for sid in self.cams}
        self.seen = {sid: 0 for sid in self.cams}
        self.last = {}

    def observe(self, results, in_window: bool) -> None:
        for r in results:
            if r.stream_id not in self.cams:
                continue
            blocks = self.blocks[r.stream_id]
            if r.frame_idx != len(blocks):
                raise RuntimeError(f"{r.stream_id}: tick {r.frame_idx} served out of "
                                   f"order (expected {len(blocks)})")
            block = np.asarray(r.block_mask, bool)
            blocks.append(block)
            counts = np.asarray(r.counts)
            eff = self.eff[r.stream_id]
            if eff is None:
                eff = self.eff[r.stream_id] = np.zeros(counts.shape, np.float32)
            win = self.window_keep(block, self.cfg)
            eff[win] = counts[win]
            if not in_window:
                continue
            item = (counts, np.asarray(r.logits), eff.copy())
            pool, i = self.pool[r.stream_id], self.seen[r.stream_id]
            if i < self.k:
                pool[r.frame_idx] = item
            else:
                j = int(self.rng.integers(0, i + 1))
                if j < self.k:
                    del pool[sorted(pool)[j]]
                    pool[r.frame_idx] = item
            self.seen[r.stream_id] = i + 1
            self.last[r.stream_id] = (r.frame_idx, item)

    def served(self, sid: str) -> dict:
        out = dict(self.pool[sid])
        if sid in self.last:
            t, item = self.last[sid]
            out[t] = item
        return out


def _annotate(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


# --------------------------------------------------------------------------
# serving loops


def serve_ticks(server, maps, start: int, seconds: float, fps: float, rec,
                in_window: bool, annotate, more=None, hook=None) -> dict:
    """Per-tick serving through ``StreamServer.run``: open loop at ``fps``
    ticks per second (each tick due on the schedule, whatever the server
    does), or closed loop (``fps`` 0: the next tick as soon as the server
    takes it).  Latency runs from a tick's due time to the moment ``run``
    yields that tick's results.  With ``more`` (set-up), ``more(k)`` decides
    before each tick ``k`` whether to send it, in place of the clock.
    ``hook()`` runs before each tick is sent."""
    t0 = time.perf_counter()
    stop = t0 + seconds
    due, late = {}, []
    length = len(maps)

    def ticks():
        k = 0
        while more is None or more(k):
            g = start + k
            if fps:
                d = t0 + k / fps
                if d >= stop:
                    return
                with annotate("bench:schedule_wait"):
                    wait = d - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                late.append(time.perf_counter() - d)
            else:
                d = time.perf_counter()
                if more is None and d >= stop:
                    return
            if hook is not None:
                hook()
            due[g] = d
            yield maps[g % length]
            k += 1

    lat, frames = [], 0
    it = server.run(ticks())
    while True:
        with annotate("bench:next"):
            res = next(it, None)
        if res is None:
            break
        now = time.perf_counter()
        lat.append((now - due[res[0].frame_idx], len(res)))
        frames += len(res)
        rec.observe(res, in_window)
    return {"t0": t0, "t1": time.perf_counter(), "frames": frames,
            "attempted": len(due) * len(maps[0]), "ticks": len(due), "latency": lat,
            "late": late}


def serve_segments(server, fleet, pos: list, seconds: float, seg: int, rec,
                   in_window: bool, annotate, rounds: int | None = None,
                   hook=None) -> dict:
    """Buffered footage served round-robin, one ``serve_segments`` call of
    ``seg`` frames per camera in turn, in a closed loop; ``hook()`` runs
    before each call."""
    t0 = time.perf_counter()
    stop = t0 + seconds
    frames = attempted = done = 0
    while (rounds is None and time.perf_counter() < stop) or (rounds and done < rounds):
        for i, sid in enumerate(fleet.ids):
            if hook is not None:
                hook()
            with annotate("bench:segment_stage"):
                buf = [fleet.frame(i, pos[i] + k) for k in range(seg)]
            with annotate("bench:next"):
                res = list(server.serve_segments(sid, buf, segment_length=seg))
            attempted += seg
            frames += len(res)
            pos[i] += len(res)
            rec.observe(res, in_window)
            if rounds is None and time.perf_counter() >= stop:
                break
        done += 1
    return {"t0": t0, "t1": time.perf_counter(), "frames": frames,
            "attempted": attempted, "ticks": done, "latency": [], "late": []}


class Tracer:
    """Profiles the last ``seconds`` of a traced window (the traffic's
    ``trace_seconds``): the profiler,
    the program's telemetry spans and launch annotations start between two
    ticks (or segment calls) once the window has that long left to run, and
    stop once the window has closed, so that no profiler start or write-out
    falls inside the window and the trace stays small.  ``stats0`` holds the
    server's counters at the start of the traced part."""

    def __init__(self, server, window_s: float, seconds: float, log_dir: str):
        self.server, self.log_dir = server, log_dir
        self.start_at = time.perf_counter() + max(window_s - seconds, 0.0)
        self.jsonl = Path(log_dir) / "spans.jsonl"
        self.span = None
        self.stats0 = None

    def __call__(self) -> None:
        if self.span is not None or time.perf_counter() < self.start_at:
            return
        import jax

        from bench import trace as trace_mod
        from repro.fpca import telemetry

        self.stats0 = _stats(self.server)
        telemetry.enable(self.jsonl, profile=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
        self.span.__enter__()

    def stop(self) -> None:
        import jax

        from repro.fpca import telemetry

        if self.span is None:
            raise RuntimeError("the window closed before its traced part began")
        self.span.__exit__(None, None, None)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        telemetry.disable()
        log(f"trace: stop_trace {time.perf_counter() - t:.3f} s")


def _stats(server) -> dict:
    s = server.stats
    return {k: float(getattr(s, k)) for k in s._FIELDS}


def _quantile(samples, q: float) -> float:
    """``q``-quantile over weighted samples ``[(value, weight), ...]``."""
    vals = np.repeat([v for v, _ in samples], [w for _, w in samples])
    return float(np.percentile(vals, q))


# --------------------------------------------------------------------------
# a run


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float | None = None, window: dict | None = None) -> dict:
    """Set up, warm up, measure, check; returns the result object.  A
    ``window`` dict receives the raw readings of the measured window."""
    import jax

    from bench import peaks, reference, scenes, trace as trace_mod, work

    t_start = time.perf_counter() if t_start is None else t_start
    cfg, tr = cell.cfg, cell.traffic
    s = cfg["spec"]
    devices = jax.devices()[: cell.chips]
    counter = CompileCounter()
    fleet = scenes.Fleet(s["image_h"], s["image_w"], tr["cameras"], tr["scene"], seed)
    weights = make_weights(cell, seed)
    pipe, server = build_server(cell, weights, fleet.ids)
    rec = Recorder(fleet, seed, tr["check"], cfg)
    per_tick = tr["entry"] == "tick"
    maps = fleet.tick_maps() if per_tick else None
    pos = [0] * fleet.cameras
    nothing = _annotate(False)

    # set-up: serve the traffic until the last ``warm_quiet`` ticks (or
    # two segment rounds) compiled nothing
    tick = 0
    if per_tick:
        quiet, cap = int(tr["warm_quiet"]), WARM_TICKS_MAX
        last = {"k": 0, "n": counter.n}

        def more(k):
            if counter.n != last["n"]:
                last.update(k=k, n=counter.n)
            return k < cap and k - last["k"] < quiet

        tick = serve_ticks(server, maps, 0, 0, 0, rec, False, nothing, more=more)["ticks"]
    else:
        quiet = 0
        for _ in range(WARM_CHUNKS_MAX):
            before = counter.n
            serve_segments(server, fleet, pos, 0, int(tr["segment_length"]), rec,
                           False, nothing, rounds=1)
            quiet = quiet + 1 if counter.n == before else 0
            if quiet >= 2:
                break
    jax.block_until_ready(weights)
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s, {tick} ticks, {counter.n} traces/compiles, "
        f"executable cache {pipe.cache_info()}")

    # the window
    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    tracer = Tracer(server, seconds, float(tr["trace_seconds"]), tmp.name) if trace else None
    annotate = _annotate(trace)
    compiles0 = counter.n
    if per_tick:
        out = serve_ticks(server, maps, tick, seconds, float(tr["camera_fps"]), rec,
                          True, annotate, hook=tracer)
    else:
        out = serve_segments(server, fleet, pos, seconds, int(tr["segment_length"]),
                             rec, True, annotate, hook=tracer)
    if trace:
        tracer.stop()
    window_s = out["t1"] - out["t0"]
    if window is not None:
        window.update(out)
    compiles = counter.n - compiles0
    counter.close()
    log(f"window: {window_s:.3f} s, {out['ticks']} ticks, {out['frames']} camera-frames, "
        f"{compiles} traces/compiles in the window, executable cache {pipe.cache_info()}")
    if out["late"]:
        late = np.asarray(out["late"]) * 1e3
        log(f"generator lateness: median {np.median(late):.3f} ms, "
            f"p95 {np.percentile(late, 95):.3f} ms, max {late.max():.3f} ms")
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(max(mem))}
    frames_per_s = out["frames"] / window_s if window_s > 0 else 0.0

    metrics = {}
    breakdown = None
    if not trace:
        values = {"setup_s": setup_s, "frames_per_s": frames_per_s}
        if out["latency"]:
            for q in LATENCY_QUANTILES:
                values[f"latency_p{q}_ms"] = 1e3 * _quantile(out["latency"], q)
            log("latency: " + ", ".join(f"p{q} {values[f'latency_p{q}_ms']:.3f} ms"
                                        for q in LATENCY_QUANTILES))
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from repro.fpca import telemetry

        t_load = time.perf_counter()
        tr_obj = trace_mod.load(trace_mod.find_xplane(tmp.name), devices=len(devices))
        log(f"trace: read in {time.perf_counter() - t_load:.3f} s, "
            f"{tr_obj.window_s:.3f} s traced, "
            f"{sum(len(d.ops) for d in tr_obj.devices.values())} device operations, "
            f"{len(tr_obj.host)} host annotations")
        spans = [e for e in telemetry.read_jsonl(tracer.jsonl) if e.get("event") == "span"]
        traced = {k: v - tracer.stats0[k] for k, v in _stats(server).items()}
        ctx = SimpleNamespace(
            trace=tr_obj, spans=spans, stats=traced, cfg=cfg, work=work,
            peak=peaks.peaks_for(dev0.device_kind) if dev0.platform == "tpu" else None,
            chips=len(devices), frames_per_s=frames_per_s,
            model_flops_per_frame=work.model_flops_per_frame(cfg, cell.head.head_flops(cfg)),
            note=log)
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name'].split('.')[0]}.py")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr_obj.busy_s
        device["window_s"] = tr_obj.window_s
        breakdown = {"device_ops": trace_mod.top_ops(tr_obj),
                     "idle_gaps": trace_mod.idle_gaps(tr_obj)}
        tmp.cleanup()

    # the check, once the window has closed and memory has been read
    del maps
    t_check = time.perf_counter()
    ref = reference.Reference(cfg, cell.head, fleet, weights)
    readings = reference.Readings()
    for sid, cam in rec.cams.items():
        served = rec.served(sid)
        if served:
            ref.check(cam, served, np.stack(rec.blocks[sid]), readings)
    numbers = readings.numbers()
    for k, (v, where) in sorted(readings.worst.items()):
        log(f"check: widest {k} {v!r} at camera, tick {where}")
    log("check readings: " + json.dumps(numbers))
    if window is not None:
        window["numbers"] = numbers
    checks = {k: {"value": numbers[k], "limit": cell.limits[k]} for k in cell.limits}
    failed = out["attempted"] - out["frames"]
    correct = bool(readings.frames > 0 and failed == 0
                   and all(c["value"] <= c["limit"] for c in checks.values()))
    log(f"check: {readings.frames} sampled camera-frames of {len(rec.cams)} cameras, "
        f"{time.perf_counter() - t_check:.3f} s; host peak RSS "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f} GiB")
    result = {"correct": correct, "attempted": int(out["attempted"]), "failed": int(failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def print_result(result: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error; the result as the last line of standard output."""
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
