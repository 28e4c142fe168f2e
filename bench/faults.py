"""Faults planted under the timed path, for the check to refuse.

* ``stale``: a step that returns its state unchanged: the skip-aware head's
  step hands back the effective map it was given and the head's outputs on
  it (per tick), or the segment hands back the map it was given;
* ``half``: half of the batch left out: the frontend's counts of the second
  half of a tick's cameras (per tick) or of a segment's ticks come back as
  zeros;
* ``altered``: an answer altered where it is produced: one count of every
  frame the frontend serves is raised by 7.

The exchange between chips has no fault to plant: the data-sharded frontend
computes each chip's cameras on that chip and exchanges nothing.

``plant(kind)`` patches the program for the length of a ``with`` block.
"""

from __future__ import annotations

import contextlib

import numpy as np

KINDS = ("stale", "half", "altered")


def _tick(kind: str):
    from repro.fpca.executable import CompiledModel
    from repro.serving.fpca_pipeline import FPCAPipeline

    if kind == "stale":
        orig = CompiledModel.patched_logits

        def stale(self, counts, prev_eff, window_keep, head_params=None):
            return orig(self, prev_eff, prev_eff, np.zeros_like(np.asarray(window_keep)),
                        head_params)

        return CompiledModel, "patched_logits", stale
    orig = FPCAPipeline.run_config_batch

    def half(self, name, images, window_keep=None):
        counts = orig(self, name, images, window_keep)
        return counts.at[counts.shape[0] // 2:].set(0.0)

    def altered(self, name, images, window_keep=None):
        return orig(self, name, images, window_keep).at[:, 0, 0, 0].add(7.0)

    return FPCAPipeline, "run_config_batch", {"half": half, "altered": altered}[kind]


def _segment(kind: str):
    import jax.numpy as jnp

    from repro.serving.fpca_pipeline import FPCAPipeline

    orig = FPCAPipeline.run_config_segment

    def broken(self, name, frames, *, state=None, **kw):
        eff_in = None if state is None else np.asarray(state.eff)
        seg = orig(self, name, frames, state=state, **kw)
        if kind == "stale":
            seg.state.eff = (jnp.zeros_like(seg.state.eff) if eff_in is None
                             else jnp.asarray(eff_in))
        elif kind == "half":
            seg.counts = seg.counts.at[seg.counts.shape[0] // 2:].set(0.0)
        else:
            seg.counts = seg.counts.at[:, 0, 0, 0].add(7.0)
        return seg

    return FPCAPipeline, "run_config_segment", broken


@contextlib.contextmanager
def plant(kind: str, entry: str = "tick"):
    """Plant fault ``kind`` under the serving ``entry`` (``tick`` or
    ``segments``) while the block runs."""
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; one of {KINDS}")
    owner, attr, fn = _tick(kind) if entry == "tick" else _segment(kind)
    orig = owner.__dict__[attr]
    setattr(owner, attr, fn)
    try:
        yield
    finally:
        setattr(owner, attr, orig)
