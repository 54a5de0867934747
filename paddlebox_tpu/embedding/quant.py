"""Two-plane working-set storage: the embedx plane apart from the rest.

The device working-set table of some configurations is a two-plane
pytree instead of one ``(N, row_width)`` array —

    fp : f32 (N, fixed_cols + n_opt_slots [+ 1])
                                        show, clk, w-block, optimizer
                                        state [, the per-row dequant scale]
    qx : (N, total_dim)                 embedx(+expand): int8|int16
                                        quantized, or f32 as it is

**Quantized storage** (``EmbeddingConfig(storage="int8" | "int16")``).
Reference: the Quant/ShowClk feature types store embedx quantized inside
the PS and dequantize at pull (the PullCopy quant kernel variants,
box_wrapper.cu:35-432) — trading a bounded precision loss for table
capacity. Compute stays f32: lookups dequantize at the gather
(``x = qx * scale``), and the push path reconstructs f32 rows, applies
the optimizer exactly as the f32 table does, then requantizes with a
fresh per-row scale — one fused elementwise pass, no f32 table ever
materialized in HBM. int8 cuts embedx HBM 4x (int16 2x); per-row dynamic
scaling keeps the quantization error relative (~0.4% of the row's max
magnitude at int8).

**Lane-tile f32 planes** (``storage="f32"`` at an embedx width of whole
128-lane tiles; ``working_set.plane_layout`` decides). On a TPU, XLA
stores a table of 133 floats a row column-major, so no operation can
address a row: every gather, scatter and row kernel gets a padded
row-major copy of the whole table. An ``(N, 128)`` f32 plane is stored
row-major with no padding, its rows gather and scatter in place, and the
narrow rest (``(N, 5)`` with adagrad, 84 MB at 2.6 M rows) is the only
column-major part. No scale column, no rounding: the planes hold the
row's exact f32 bits.

The HOST store stays full f32 rows either way — the planes are a
device-storage choice, like the reference's PS-side feature type, so
checkpoints/serving are full precision and switching layouts is always
safe.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from paddlebox_tpu.embedding.config import EmbeddingConfig

_QINFO = {"int8": (jnp.int8, 127.0), "int16": (jnp.int16, 32767.0)}


class PlaneTable(NamedTuple):
    fp: jnp.ndarray     # f32 (N, fixed + n_opt [+ 1]): show, clk, w*, opt
                        # [, scale]
    qx: jnp.ndarray     # (N, total_dim): int8/int16 quantized, or f32

    @property
    def shape(self) -> tuple[int, int]:
        """The LOGICAL (rows, row_width) — what the one-array table of
        the same configuration would report (the scale column is the
        quantized planes' own)."""
        scale = 0 if self.qx.dtype == jnp.float32 else 1
        return (self.fp.shape[0],
                self.fp.shape[1] - scale + self.qx.shape[1])


def is_planes(table) -> bool:
    return isinstance(table, PlaneTable)


def is_quant(table) -> bool:
    """A plane table whose embedx plane is quantized."""
    return is_planes(table) and table.qx.dtype != jnp.float32


def table_rows(table) -> int:
    return table.shape[0]


def row_engine_width(table) -> int | None:
    """Columns of the f32 array whose whole rows a row-wise push engine
    gathers and writes back (``resolve_push_engine``'s ``table_width``):
    the one array's physical width, the f32 embedx plane's, None for
    quantized planes (no f32 row to move)."""
    if not is_planes(table):
        return int(table.shape[1])
    return None if is_quant(table) else int(table.qx.shape[1])


def qdtype(cfg: EmbeddingConfig):
    return _QINFO[cfg.storage][0]


def qmax(cfg: EmbeddingConfig) -> float:
    return _QINFO[cfg.storage][1]


def fp_width(cfg: EmbeddingConfig) -> int:
    return cfg.fixed_cols + cfg.n_opt_slots + (cfg.storage != "f32")


# ---------------------------------------------------------------------------
# generic per-row quantization (host) — shared by the device working-set
# planes below and the serving publisher's cold-row artifact compression
# (serving/artifact.py): one rule for "f32 matrix → (q, scale) planes".
# ---------------------------------------------------------------------------

def quantize_rows_np(x: np.ndarray, storage: str
                     ) -> tuple[np.ndarray, np.ndarray]:
    """f32 (N, D) → (q int8/int16 (N, D), scale f32 (N,)) with per-row
    dynamic scaling (quantization error stays relative to each row's max
    magnitude). D == 0 degenerates cleanly."""
    dt, qm = _QINFO[storage]
    x = np.asarray(x, np.float32)
    scale = (np.abs(x).max(axis=1) / qm if x.shape[1]
             else np.zeros(len(x), np.float32))
    scale = np.maximum(scale, 1e-12).astype(np.float32)
    q = np.round(x / scale[:, None]).astype(np.dtype(dt.__name__))
    return q, scale


def dequantize_rows_np(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """(q, scale) planes → f32 rows (the inverse of quantize_rows_np,
    up to the bounded rounding error)."""
    return q.astype(np.float32) * np.asarray(scale, np.float32)[:, None]


def quantize_lanes(x: jnp.ndarray, storage: str
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Traced twin of quantize_rows_np over the LAST axis: (..., D) f32
    → (q int8/int16 (..., D), scale f32 (...,)) with per-lane dynamic
    scaling. The exchange's push-wire compression rides this so the
    f32→(q, scale) rule stays in one place."""
    dt, qm = _QINFO[storage]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1) / qm, 1e-12)
    q = jnp.round(x / scale[..., None]).astype(dt)
    return q, scale


def dequantize_lanes(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of quantize_lanes (up to the bounded rounding error)."""
    return q.astype(jnp.float32) * scale[..., None]


# ---------------------------------------------------------------------------
# plane <-> full-f32-row conversions (host + traced)
# ---------------------------------------------------------------------------

def encode_rows_np(rows: np.ndarray, cfg: EmbeddingConfig
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Host-side f32 rows → (fp, qx) planes."""
    narrow = [rows[:, :cfg.fixed_cols], rows[:, cfg.opt_cols]]
    if cfg.storage == "f32":
        qx = np.ascontiguousarray(rows[:, cfg.embedx_cols], np.float32)
    else:
        qx, scale = quantize_rows_np(rows[:, cfg.embedx_cols], cfg.storage)
        narrow.append(scale[:, None])
    return np.concatenate(narrow, axis=1).astype(np.float32), qx


def decode_rows_np(fp: np.ndarray, qx: np.ndarray,
                   cfg: EmbeddingConfig) -> np.ndarray:
    fc = cfg.fixed_cols
    rows = np.empty((len(fp), cfg.row_width), np.float32)
    rows[:, :fc] = fp[:, :fc]
    rows[:, cfg.embedx_cols] = (qx if cfg.storage == "f32" else
                                qx.astype(np.float32) * fp[:, -1:])
    rows[:, cfg.opt_cols] = fp[:, fc:fc + cfg.n_opt_slots]
    return rows


def assemble_rows(fp: jnp.ndarray, qx: jnp.ndarray,
                  cfg: EmbeddingConfig) -> jnp.ndarray:
    """Traced planes → full f32 rows (fuses into the consumer)."""
    fc = cfg.fixed_cols
    x = qx if cfg.storage == "f32" else qx.astype(jnp.float32) * fp[:, -1:]
    return jnp.concatenate([fp[:, :fc], x, fp[:, fc:fc + cfg.n_opt_slots]],
                           axis=1)


def split_rows(rows: jnp.ndarray, cfg: EmbeddingConfig) -> PlaneTable:
    """Traced full f32 rows → planes (quantized storage: requantized
    with a fresh per-row scale)."""
    x = rows[:, cfg.embedx_cols]
    narrow = [rows[:, :cfg.fixed_cols], rows[:, cfg.opt_cols]]
    if cfg.storage == "f32":
        return PlaneTable(fp=jnp.concatenate(narrow, axis=1), qx=x)
    if cfg.total_dim:
        scale = jnp.maximum(jnp.abs(x).max(axis=1) / qmax(cfg), 1e-12)
    else:
        scale = jnp.full((rows.shape[0],), 1e-12, jnp.float32)
    qx = jnp.round(x / scale[:, None]).astype(qdtype(cfg))
    return PlaneTable(fp=jnp.concatenate([*narrow, scale[:, None]], axis=1),
                      qx=qx)


def device_planes(host_rows: np.ndarray, cfg: EmbeddingConfig, sharding
                  ) -> PlaneTable:
    """`host_rows` (full f32 rows) as a plane table on the device."""
    fp, qx = encode_rows_np(host_rows, cfg)
    if sharding is not None:
        return PlaneTable(*jax.device_put((fp, qx), sharding))
    return PlaneTable(fp=jnp.asarray(fp), qx=jnp.asarray(qx))
