"""The plain reference that decides ``correct``, and its comparison.

Nothing here imports the program.  The reference follows the FPCA paper's
sensor model as the configuration states it:

* the circuit oracle (``core/device_models``) and the paper's two-step
  bucket-select curvefit fitted against it (``core/curvefit``), refitted here
  in float64 on the host;
* the bucket-sigmoid prediction of both weight phases, the NVM weight
  encoding and the up/down SS-ADC readout (``core/fpca_sim``, ``core/adc``),
  in float32 on the device with every contraction at ``HIGHEST`` precision;
* the temporal delta gate (``core/gating``) replayed in float64 on the host;
* the skip-aware head: each window keeps the counts of the last tick that
  kept it, and the configuration's own head module runs on that map, built
  from the counts the program served (as a served model's check runs the
  reference over its served tokens); the counts themselves are compared
  with the reference's.

The same functions run in bfloat16 as the control (``dtype=jnp.bfloat16``):
the reference computed one precision below the float32 the configuration
states, which the comparison has to refuse.

The comparison reads, over a sample of served camera-frames drawn from the
seed:

* ``gate_miss``: block keep decisions that differ from the reference's,
  leaving out blocks whose change lay within ``GATE_BAND`` of the threshold
  in the last ``hysteresis + 1`` ticks (a float32 mean may round either way
  there);
* ``count_miss_pct``: the share of counts (every window and channel of a
  sampled tick, skipped windows read as zero) that differ from the reference;
* ``count_max_err``: the largest count difference;
* ``head_err``: the largest difference between the served head outputs and
  the reference head run on the effective map that the served counts build,
  over the RMS of the latter: the head alone, as a served model's check
  reads the reference over its served tokens.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

GATE_BAND = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 4096          # windows per reference block
FRAME_BUCKET = 16    # the reference's frame batches are padded to a multiple


# --------------------------------------------------------------------------
# circuit oracle and bucket curvefit, float64 on the host


def _pixel_drive(I, W, c):
    iw = I * W
    num = iw + c["drive_a"] * (I * iw) + c["drive_b"] * (W * iw)
    g = num / (1.0 + c["drive_c"] * iw)
    return g / (1.0 + c["kappa_r"] * c["r_metal_mm"] * g)


def _oracle(I, W, c, n_pixels):
    """Bitline voltage: fixed point of the coupled tanh over the last axis."""
    s = np.sum(_pixel_drive(I, W, c), axis=-1)
    denom = n_pixels * c["s0"]
    v = c["v_sat"] * np.tanh(s / denom)
    for _ in range(c["fp_iters"]):
        v = c["v_sat"] * np.tanh((1.0 - c["coupling"] * v / c["v_sat"]) * s / denom)
    return v


def _exps(degree):
    return np.array([(a, t - a) for t in range(degree + 1) for a in range(t + 1)])


def _design(I, W, exps):
    return np.stack([I**a * W**b for a, b in exps], axis=-1)


def _fit(I, W, V, degree):
    exps = _exps(degree)
    A = _design(I.ravel(), W.ravel(), exps)
    coeffs, *_ = np.linalg.lstsq(A, V.ravel(), rcond=None)
    return coeffs, exps


def fit_bucket_model(cfg: dict) -> dict:
    """The paper's two fitting set-ups against the circuit oracle."""
    c, f = cfg["circuit"], cfg["curvefit"]
    n = cfg["spec"]["max_kernel"] ** 2 * cfg["spec"]["in_channels"]
    g = np.linspace(0.0, 1.0, f["grid"])
    gi, gw = np.meshgrid(g, g, indexing="ij")

    def shared(ti, tw):
        ti, tw = np.asarray(ti, np.float64), np.asarray(tw, np.float64)
        return _oracle(np.repeat(ti[..., None], n, -1), np.repeat(tw[..., None], n, -1),
                       c, n)

    avg, avg_exps = _fit(gi, gw, shared(gi, gw), f["degree_avg"])
    nb, ns = f["n_buckets"], f["n_sweep"]
    buckets, v_centers = [], []
    for b in range(nb):
        target = (b + 0.5) / nb * c["v_sat"]
        lo, hi = 0.0, 1.0
        for _ in range(40):
            mid = 0.5 * (lo + hi)
            if shared(mid, mid) < target:
                lo = mid
            else:
                hi = mid
        t = 0.5 * (lo + hi)
        I = np.concatenate([np.repeat(gi[..., None], ns, -1),
                            np.full(gi.shape + (n - ns,), t)], -1)
        W = np.concatenate([np.repeat(gw[..., None], ns, -1),
                            np.full(gw.shape + (n - ns,), t)], -1)
        coeffs, buc_exps = _fit(gi, gw, _oracle(I, W, c, n), f["degree_buc"])
        buckets.append(coeffs)
        v_centers.append(float(shared(t, t)))
    return {"avg": avg, "avg_exps": avg_exps, "buc": np.stack(buckets),
            "buc_exps": buc_exps, "v_centers": np.array(v_centers),
            "n_pixels": n, "n_sweep": ns, "v_range": c["v_sat"],
            "sharpness": f["sharpness"]}


# --------------------------------------------------------------------------
# frontend counts on the device


def encode_weights(kernel, cfg: dict):
    """Float kernel ``(C, k, k, c_i)`` -> (positive, negative) conductance
    planes ``(C, c_i * n * n)``, quantised to the NVM levels, channel-major."""
    s, e = cfg["spec"], cfg["enc"]
    n, k = s["max_kernel"], s["kernel"]
    w01 = jnp.clip(jnp.abs(kernel) / e["w_scale"], 0.0, 1.0)

    def plane(w):
        w = jnp.round(w * (e["n_levels"] - 1)) / (e["n_levels"] - 1)
        w = jnp.transpose(w, (0, 3, 1, 2))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, n - k), (0, n - k)))
        return w.reshape(w.shape[0], -1)

    return plane(jnp.where(kernel > 0, w01, 0.0)), plane(jnp.where(kernel < 0, w01, 0.0))


def windows(frames, cfg: dict):
    """``(b, H, W, c_i)`` -> ``(b * h_o * w_o, c_i * n * n)`` photocurrents
    (stride == kernel, no padding, no binning: the configurations here)."""
    s = cfg["spec"]
    n = s["max_kernel"]
    if s["stride"] != n or s["padding"] or s["binning"] != 1:
        raise ValueError("the reference reads stride == max_kernel, padding 0, binning 1")
    b, h, w, c = frames.shape
    h_o, w_o = h // n, w // n
    t = frames[:, : h_o * n, : w_o * n].reshape(b, h_o, n, w_o, n, c)
    return t.transpose(0, 1, 3, 5, 2, 4).reshape(b * h_o * w_o, c * n * n)


def _predict(I, W, fit, dtype, precision):
    """Bucket-sigmoid bitline voltage of windows ``I (m, N)`` read against
    planes ``W (C, N)``: ``(m, C)``."""
    cast = lambda x: jnp.asarray(x, dtype)
    n = fit["n_pixels"]
    mi, mw = jnp.mean(I, -1)[:, None], jnp.mean(W, -1)[None, :]
    v_est = sum(cast(cf) * mi**a * mw**b for cf, (a, b) in zip(fit["avg"], fit["avg_exps"]))
    ip = {a: I**a for a in range(4)}
    wp = {b: W**b for b in range(4)}
    sums = {(a, b): jnp.einsum("mn,cn->mc", ip[a], wp[b], precision=precision)
            for a, b in fit["buc_exps"]}
    x = v_est / cast(fit["v_range"])
    nb, k = fit["buc"].shape[0], cast(fit["sharpness"])
    v = jnp.zeros_like(x)
    for i in range(nb):
        s = sum(cast(fit["buc"][i, t]) * sums[(int(a), int(b))]
                for t, (a, b) in enumerate(fit["buc_exps"]))
        vc = cast(fit["v_centers"][i])
        pred = (s - cast(n) * vc) / cast(fit["n_sweep"]) + vc
        gate = (jax.nn.sigmoid(k * (x - cast(i / nb)))
                + jax.nn.sigmoid(k * (cast((i + 1) / nb) - x)) - cast(1.0))
        v = v + gate * pred
    return v


def make_counts_fn(cfg: dict, fit: dict, dtype=jnp.float32):
    """Jitted ``(frames, kernel, bn) -> (b, h_o, w_o, C)`` counts, evaluated in
    blocks of ``ROWS`` windows."""
    precision = HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    s, a = cfg["spec"], cfg["adc"]
    n = s["max_kernel"]
    lsb, levels = a["v_ref"] / 2 ** a["bits"], 2 ** a["bits"]

    def quant(v):
        return jnp.clip(jnp.round(v / jnp.asarray(lsb, v.dtype)), 0, levels - 1)

    @jax.jit
    def counts(frames, kernel, bn):
        b, h, w, _ = frames.shape
        I = windows(frames, cfg).astype(dtype)
        m = I.shape[0]
        pad = -m % ROWS
        I = jnp.pad(I, ((0, pad), (0, 0))).reshape(-1, ROWS, I.shape[-1])
        wpos, wneg = (p.astype(dtype) for p in encode_weights(kernel, cfg))
        bnc = bn.astype(dtype)

        def block(x):
            up = quant(_predict(x, wpos, fit, dtype, precision))
            down = quant(_predict(x, wneg, fit, dtype, precision))
            return jnp.clip(bnc + up - down, 0, levels - 1)

        out = jax.lax.map(block, I).reshape(-1, wpos.shape[0])[:m]
        return out.astype(jnp.float32).reshape(b, h // n, w // n, -1)

    return counts


# --------------------------------------------------------------------------
# delta gate replay, float64 on the host


def window_keep(block_keep: np.ndarray, cfg: dict) -> np.ndarray:
    """A window executes iff any of its pixels lies in a kept block."""
    s = cfg["spec"]
    b, n = s["skip_block"], s["max_kernel"]
    h_o, w_o = s["image_h"] // n, s["image_w"] // n
    pix = np.kron(block_keep, np.ones((b, b), bool))[: s["image_h"], : s["image_w"]]
    return pix[: h_o * n, : w_o * n].reshape(h_o, n, w_o, n).any(axis=(1, 3))


def clip_deltas(clip, cfg: dict, dtype=jnp.float64) -> np.ndarray:
    """Mean |change| per block from each frame of a looping clip to the next:
    entry ``j`` compares frame ``j`` with frame ``j - 1`` (cyclically).
    Float64 on the host for the reference; ``jnp.bfloat16`` on the device
    for the control."""
    blk = cfg["spec"]["skip_block"]
    if dtype == jnp.float64:
        eff = np.asarray(clip, np.float64).mean(-1)
        d = np.abs(eff - np.roll(eff, 1, axis=0))
    else:
        eff = jnp.mean(jnp.asarray(clip, dtype), -1).astype(dtype)
        d = jnp.abs(eff - jnp.roll(eff, 1, axis=0))
    t, h, w = d.shape
    bh, bw = math.ceil(h / blk), math.ceil(w / blk)
    ones = np.zeros((bh * blk, bw * blk))
    ones[:h, :w] = 1
    cnt = ones.reshape(bh, blk, bw, blk).sum((1, 3))
    if dtype == jnp.float64:
        pad = np.zeros((t, bh * blk, bw * blk))
        pad[:, :h, :w] = d
        return pad.reshape(t, bh, blk, bw, blk).sum((2, 4)) / cnt
    pad = jnp.pad(d, ((0, 0), (0, bh * blk - h), (0, bw * blk - w)))
    sums = pad.reshape(t, bh, blk, bw, blk).sum((2, 4), dtype=dtype)
    return np.asarray((sums / jnp.asarray(cnt, dtype)).astype(jnp.float32), np.float64)


def stream_deltas(fleet, cam: int, ticks: int, per_clip) -> np.ndarray:
    """``(ticks, bh, bw)`` block changes of one camera served from tick 0
    (tick 0 has no previous frame and reads 0)."""
    d = per_clip[fleet.clip_of[cam]]
    idx = (np.arange(ticks) + fleet.phase[cam]) % fleet.length
    out = d[idx]
    out[0] = 0.0
    return out


def gate_replay(deltas: np.ndarray, gate: dict) -> np.ndarray:
    """Block keep grids ``(T, bh, bw)`` of a stream served from tick 0."""
    thr, hyst, ki = gate["threshold"], gate["hysteresis"], gate["keyframe_interval"]
    age = np.full(deltas.shape[1:], hyst + 1, np.int64)
    keep = np.empty(deltas.shape, bool)
    for t in range(deltas.shape[0]):
        if t > 0:
            age = np.where(deltas[t] > thr, 0, age + 1)
        key = t == 0 or (ki > 0 and t % ki == 0)
        keep[t] = True if key else age <= hyst
    return keep


def gate_misses(prog_keep: np.ndarray, deltas: np.ndarray, gate: dict) -> int:
    """Keep decisions that differ from the reference, outside the band."""
    ref = gate_replay(deltas, gate)
    near = np.abs(deltas - gate["threshold"]) <= GATE_BAND
    near[0] = False
    amb = near.copy()
    for lag in range(1, gate["hysteresis"] + 1):
        amb[lag:] |= near[:-lag]
    return int(((ref != prog_keep) & ~amb).sum())


# --------------------------------------------------------------------------
# the skip-aware head and the comparison


def effective_maps(counts_at: dict, win: dict, ticks, first: dict, shape) -> np.ndarray:
    """Effective count maps at ``ticks``: each window holds the counts of the
    last tick, from ``first[t]`` on, that kept it (zeros if none did)."""
    out = np.zeros((len(ticks),) + shape, np.float32)
    for k, t in enumerate(ticks):
        for s in range(first[t], t + 1):
            m = win[s]
            out[k][m] = counts_at[s][m]
    return out


class Readings:
    """Accumulates the compared numbers over the sampled camera-frames."""

    def __init__(self):
        self.gate_miss = 0
        self.count_miss = 0
        self.counts = 0
        self.count_max_err = 0.0
        self.head_abs = 0.0
        self.head_sq = 0.0
        self.head_n = 0
        self.frames = 0
        self.worst = {}

    def add_gate(self, misses: int):
        self.gate_miss += int(misses)

    def _worst(self, name, value, where):
        if value >= self.worst.get(name, (-1.0, None))[0]:
            self.worst[name] = (float(value), where)

    def add(self, counts, ref_counts, logits, head_logits, where=()):
        """``head_logits``: the reference head on the effective map that the
        served counts build, beside the served ``logits``."""
        d = np.abs(np.asarray(counts, np.float64) - np.asarray(ref_counts, np.float64))
        self.count_miss += int((d > 0).sum())
        self.counts += d.size
        self.count_max_err = max(self.count_max_err, float(d.max()))
        lg = np.asarray(logits, np.float64)
        h = np.asarray(head_logits, np.float64)
        herr = np.abs(lg - h).reshape(len(lg), -1).max(-1)
        for k, tick in enumerate(where):
            self._worst("head_err", herr[k], tick)
            self._worst("count_max_err", d[k].max(), tick)
        self.head_abs = max(self.head_abs, float(herr.max()))
        self.head_sq += float((h * h).sum())
        self.head_n += h.size
        self.frames += np.shape(counts)[0]

    def numbers(self) -> dict:
        head_rms = math.sqrt(self.head_sq / max(self.head_n, 1))
        return {
            "gate_miss": float(self.gate_miss),
            "count_miss_pct": 100.0 * self.count_miss / max(self.counts, 1),
            "count_max_err": self.count_max_err,
            "head_err": self.head_abs / head_rms if head_rms > 0 else self.head_abs,
        }


class Reference:
    """The reference of one cell: its fit, weights and compiled functions."""

    def __init__(self, cfg: dict, head_mod, fleet, weights: dict):
        self.cfg, self.head_mod, self.fleet, self.weights = cfg, head_mod, fleet, weights
        self.fit = fit_bucket_model(cfg)
        self.counts_fn = make_counts_fn(cfg, self.fit)
        self.deltas = [clip_deltas(c, cfg) for c in fleet.pool]

    def _expected(self, cam: int, keep_blocks: np.ndarray, ticks):
        """The window keep grids and the reference's counts at every tick
        that the sampled ticks' effective maps read, and the first such tick
        of each."""
        ki = self.cfg["gate"]["keyframe_interval"]
        # a keyframe keeps every window, so nothing before it is read
        first = {t: (t - t % ki if ki else 0) for t in ticks}
        win = {s: window_keep(keep_blocks[s], self.cfg)
               for t in ticks for s in range(first[t], t + 1)}
        return win, first, self._counts_at(self.counts_fn, cam, sorted(win))

    def _counts_at(self, fn, cam, need):
        """The reference's counts at ticks ``need`` of one camera; the batch
        is padded to a multiple of ``FRAME_BUCKET`` frames so that a few
        shapes compile, whatever the sample."""
        frames = [self.fleet.frame(cam, s) for s in need]
        frames += frames[-1:] * (-len(frames) % FRAME_BUCKET)
        out = np.asarray(fn(jnp.asarray(np.stack(frames)), self.weights["kernel"],
                            self.weights["bn"]))
        return dict(zip(need, out))

    def check(self, cam: int, served: dict, prog_blocks: np.ndarray,
              readings: Readings) -> None:
        """One sampled camera: ``prog_blocks`` are the block keep grids the
        program reported for its ticks ``0 .. T-1``; ``served`` maps sampled
        ticks to the program's ``(counts, head outputs, effective map)``,
        the map built from its served counts."""
        deltas = stream_deltas(self.fleet, cam, prog_blocks.shape[0], self.deltas)
        readings.add_gate(gate_misses(prog_blocks, deltas, self.cfg["gate"]))
        ticks = sorted(served)
        win, _, ref_at = self._expected(cam, prog_blocks, ticks)
        counts = np.stack([np.asarray(served[t][0]) for t in ticks])
        head_out = self._head(np.stack([served[t][2] for t in ticks]))
        out = np.stack([np.asarray(served[t][1]) for t in ticks]).reshape(head_out.shape)
        readings.add(counts, np.stack([ref_at[t] * win[t][..., None] for t in ticks]),
                     out, head_out, where=[(cam, t) for t in ticks])

    def _head(self, eff, params=None, dtype=jnp.float32):
        """The configuration's head reference on effective maps ``eff``, in
        ``dtype`` (``HIGHEST`` precision in float32)."""
        params = self.weights["head"] if params is None else params
        precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        return np.asarray(self.head_mod.head(params, jnp.asarray(eff, dtype), self.cfg,
                                             precision=precision), np.float32)

    def control(self, cam: int, ticks, n_ticks: int, readings: Readings) -> None:
        """The control in the program's place: the reference computed in
        bfloat16 over the same sampled ticks of one camera."""
        cfg, gate = self.cfg, self.cfg["gate"]
        if not hasattr(self, "_c_counts"):
            self._c_counts = make_counts_fn(cfg, self.fit, jnp.bfloat16)
            self._c_deltas = [clip_deltas(c, cfg, jnp.bfloat16) for c in self.fleet.pool]
            self._c_head = jax.tree.map(lambda a: jnp.asarray(a, jnp.bfloat16),
                                        self.weights["head"])
        deltas = stream_deltas(self.fleet, cam, n_ticks, self.deltas)
        c_deltas = stream_deltas(self.fleet, cam, n_ticks, self._c_deltas)
        readings.add_gate(gate_misses(gate_replay(c_deltas, gate), deltas, gate))
        ticks = sorted(ticks)
        win, first, ref_at = self._expected(cam, gate_replay(deltas, gate), ticks)
        c_at = self._counts_at(self._c_counts, cam, sorted(win))
        c_eff = effective_maps(c_at, win, ticks, first, ref_at[ticks[0]].shape)
        out = self._head(c_eff, self._c_head, jnp.bfloat16)
        readings.add(np.stack([c_at[t] * win[t][..., None] for t in ticks]),
                     np.stack([ref_at[t] * win[t][..., None] for t in ticks]),
                     out, self._head(c_eff), where=[(cam, t) for t in ticks])
