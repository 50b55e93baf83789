"""Fused per-device window feature extraction (Pallas TPU kernel).

Computes analytics features over the HBM-resident telemetry windows
(models/windows.py, [M, W, C] float32): per (device, channel) mean, std,
min, max, last value, and first-to-last delta — the feature front-end for
anomaly scoring and drift detection in the tpu-analytics service, and the
input normalization pass for models/anomaly.py.

The Pallas kernel makes this ONE pass over HBM per tile (six reductions
fused in VMEM, single read of the window data), where the naive jnp
version materializes multiple reduction intermediates. The reference has
no equivalent: it re-queries time-series DBs for any analysis. A jnp
reference implementation is used on the CPU backend and as the test
oracle.

Feature layout (axis -1): [mean, std, min, max, last, delta].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NUM_FEATURES = 6


def window_features_reference(windows: jax.Array) -> jax.Array:
    """jnp oracle: [M, W, C] -> [M, C, NUM_FEATURES]."""
    mean = jnp.mean(windows, axis=1)
    std = jnp.std(windows, axis=1)
    mn = jnp.min(windows, axis=1)
    mx = jnp.max(windows, axis=1)
    last = windows[:, -1, :]
    delta = windows[:, -1, :] - windows[:, 0, :]
    return jnp.stack([mean, std, mn, mx, last, delta], axis=-1)


def _features_kernel(win_ref, out_ref):
    """One tile: win [TM, C, W] -> out [TM, C, F].

    The window axis W sits on the TPU lane dimension (width 128-friendly),
    so reductions run across lanes and the narrow channel axis (typically 8)
    lives on sublanes — the [.., W, C] layout would pad C to 128 lanes and
    blow VMEM 16x."""
    w = win_ref[:]                       # [TM, C, W]
    n = w.shape[2]
    mean = jnp.mean(w, axis=2)           # [TM, C]
    # population std to match jnp.std
    var = jnp.mean(jnp.square(w), axis=2) - jnp.square(mean)
    std = jnp.sqrt(jnp.maximum(var, 0.0))
    mn = jnp.min(w, axis=2)
    mx = jnp.max(w, axis=2)
    last = w[:, :, n - 1]
    delta = last - w[:, :, 0]
    out_ref[:] = jnp.stack([mean, std, mn, mx, last, delta], axis=-1)


# Scoped VMEM a kernel may use on v5e: 16 MiB. Compiles for v5e (PR 21)
# put the kernel's need at about nine padded [C, W] window tiles per row
# of a grid step (input blocks double-buffered, the output block padded to
# a 128-lane tile, the squares and reductions in between), so a row is
# budgeted at ten.
_VMEM_BUDGET = 16 * 2**20


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def _tile_rows(m: int, c: int, w: int, tile_m: int) -> int:
    """Rows per grid step: at most ``tile_m`` and what the VMEM budget
    holds for [C, W] windows, a power of two so tiles divide M."""
    per_row = 10 * 4 * _round_up(c, 8) * _round_up(w, 128)
    fit = max(1, _VMEM_BUDGET // per_row)
    return 1 << (min(tile_m, fit, m).bit_length() - 1)


def window_features_pallas(windows: jax.Array, tile_m: int = 256,
                           interpret: bool = False) -> jax.Array:
    """The Pallas kernel, [M, W, C] -> [M, C, NUM_FEATURES], on any backend
    that compiles it (``interpret=True`` runs it through the interpreter)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, w, c = windows.shape
    tile = _tile_rows(m, c, w, tile_m)
    mp = _round_up(m, tile)
    if mp != m:
        windows = jnp.pad(windows, ((0, mp - m), (0, 0), (0, 0)))
    wt = jnp.swapaxes(windows.astype(jnp.float32), 1, 2)  # [M, C, W]
    out = pl.pallas_call(
        _features_kernel,
        grid=(mp // tile,),
        in_specs=[pl.BlockSpec((tile, c, w), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tile, c, NUM_FEATURES), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, c, NUM_FEATURES), jnp.float32),
        name="window_features",
        interpret=interpret,
    )(wt)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def window_features(windows: jax.Array, tile_m: int = 256,
                    interpret: bool = False) -> jax.Array:
    """[M, W, C] -> [M, C, NUM_FEATURES]. The jnp reference on a CPU
    backend; the Pallas kernel everywhere else, where a compile error
    raises. ``interpret=True`` runs the kernel in interpret mode on any
    backend (the CPU tests)."""
    if jax.default_backend() == "cpu" and not interpret:
        return window_features_reference(windows)
    return window_features_pallas(windows, tile_m, interpret)


# devicewatch (ISSUE 11): the analytics feature extractor (Pallas on
# TPU) reports compiles under its own family — a window-shape churn in
# the anomaly service shows up here, not as silent recompile stalls.
from sitewhere_tpu.utils.devicewatch import watched_jit  # noqa: E402

window_features = watched_jit(window_features, family="window_features",
                              static_argnames=("tile_m", "interpret"))


def normalize_windows(windows: jax.Array, features: jax.Array,
                      eps: float = 1e-6) -> jax.Array:
    """Standardize windows with the extracted per-channel mean/std — the
    input conditioning for the anomaly models."""
    mean = features[:, :, 0][:, None, :]
    std = features[:, :, 1][:, None, :]
    return (windows - mean) / (std + eps)
