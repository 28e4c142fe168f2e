"""Camera scenes for the benchmark's traffic, rendered once in set-up.

The scene is the benchmark's own copy of the repository's synthetic
moving-object stream (``data/pipeline.SyntheticMovingObject``): a static
low-frequency cluttered background with one Gaussian blob orbiting the
centre.  Two extensions come from the traffic file:

* ``flicker``: a global brightness offset per frame, alternating in sign with
  a magnitude drawn in ``[flicker/2, flicker]``, so every block of the frame
  changes by about ``flicker`` from one frame to the next (rain, panning,
  lighting changes: traffic that defeats the delta gate);
* ``zipf``: clip ``j`` moves at ``speed * (j + 1) ** -zipf``, and cameras are
  assigned to clips in contiguous runs, so motion is skewed across cameras
  and across the data shards that hold consecutive cameras.

A fleet replays a pool of ``clips`` clips of ``clip_frames`` frames each;
camera ``i`` plays clip ``i * clips // cameras`` from its own phase.  The
frame of camera ``i`` at tick ``t`` is a pure function of the seed, so the
reference can find every frame again.
"""

from __future__ import annotations

import numpy as np


def render_clip(h: int, w: int, seed: int, *, radius: float, speed: float,
                amplitude: float, flicker: float, frames: int) -> np.ndarray:
    """``(frames, h, w, 3)`` float32 clip in ``[0, 1]``."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(0.05, 0.35, (h // 8 + 1, w // 8 + 1, 3))
    background = np.clip(np.kron(base, np.ones((8, 8, 1)))[:h, :w], 0.0, 1.0)
    background = background.astype(np.float32)
    color = rng.uniform(0.6, 1.0, 3).astype(np.float32)
    mags = rng.uniform(0.5, 1.0, frames)
    reach = int(np.ceil(6 * radius))        # the blob is < 1e-8 beyond 6 radii
    out = np.empty((frames, h, w, 3), np.float32)
    for t in range(frames):
        cy = h / 2 + 0.30 * h * np.sin(speed * t)
        cx = w / 2 + 0.30 * w * np.cos(speed * t)
        y0, y1 = max(int(cy) - reach, 0), min(int(cy) + reach + 1, h)
        x0, x1 = max(int(cx) - reach, 0), min(int(cx) + reach + 1, w)
        yy, xx = np.mgrid[y0:y1, x0:x1]
        d2 = (yy - cy) ** 2 + (xx - cx) ** 2
        frame = background.copy()
        frame[y0:y1, x0:x1] += (
            amplitude * np.exp(-d2 / (2.0 * radius**2))
        )[..., None].astype(np.float32) * color
        if flicker:
            sign = 1.0 if t % 2 == 0 else -1.0
            frame = frame + np.float32(sign * flicker * mags[t])
        out[t] = np.clip(frame, 0.0, 1.0)
    return out


class Fleet:
    """The frames of every camera of one traffic mix, from one seed."""

    def __init__(self, h: int, w: int, cameras: int, scene: dict, seed: int):
        self.cameras = int(cameras)
        self.clips = min(int(scene["clips"]), self.cameras)
        self.length = int(scene["clip_frames"])
        ss = np.random.SeedSequence(int(seed))
        clip_seeds = ss.generate_state(self.clips)
        self.pool = [
            render_clip(
                h, w, int(clip_seeds[j]),
                radius=float(scene["radius"]),
                speed=float(scene["speed"]) * (j + 1) ** -float(scene["zipf"]),
                amplitude=float(scene["amplitude"]),
                flicker=float(scene["flicker"]),
                frames=self.length,
            )
            for j in range(self.clips)
        ]
        rng = np.random.default_rng(ss.spawn(1)[0])
        self.clip_of = [i * self.clips // self.cameras for i in range(self.cameras)]
        self.phase = rng.integers(0, self.length, self.cameras)
        self.ids = [f"cam{i:04d}" for i in range(self.cameras)]

    def frame(self, cam: int, tick: int) -> np.ndarray:
        return self.pool[self.clip_of[cam]][(tick + self.phase[cam]) % self.length]

    def tick_maps(self) -> list[dict]:
        """One ``{camera id: frame}`` map per tick of the replay period; tick
        ``t`` of the fleet is entry ``t % length``."""
        return [
            {sid: self.frame(i, t) for i, sid in enumerate(self.ids)}
            for t in range(self.length)
        ]
