"""Required work of TM inference, and the chips' published peaks.

"Required" is what any implementation of the served answer must do,
whatever backend serves it:

- operations per row: one AND and one accumulate per included literal,
  plus one vote per clause: ``2 * nnz + C * M``;
- bytes per batch of ``B`` rows: the packed literals in (``B * 2F / 8``),
  the include set in its smaller form (2 bytes per included index, or the
  ``C * M * 2F / 8`` bitmap), and the int32 class sums out (``B * C * 4``).

The least time of a batch is the larger of its operations over the int8
peak and its bytes over the memory bandwidth.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def ops_per_row(cfg: dict, nnz: int) -> int:
    """Required operations for one row."""
    return 2 * int(nnz) + int(cfg["n_classes"]) * int(cfg["n_clauses"])


def batch_bytes(cfg: dict, nnz: int, rows: int) -> int:
    """Required bytes moved for one batch of ``rows`` rows."""
    c, m, f = (int(cfg[k]) for k in ("n_classes", "n_clauses",
                                     "n_features"))
    literals = rows * 2 * f // 8
    include = min(2 * int(nnz), c * m * 2 * f // 8)
    return literals + include + rows * c * 4


def least_time(cfg: dict, nnz: int, rows: int, peak: dict
               ) -> tuple[float, str]:
    """(least seconds for one batch, "ops" or "bytes": which bound sets
    it)."""
    t_ops = rows * ops_per_row(cfg, nnz) / peak["int8_ops"]
    t_bytes = batch_bytes(cfg, nnz, rows) / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def roofline_pct(least_s: float, measured_s: float) -> float | None:
    """Share of the roofline in percent; None where nothing was measured."""
    if measured_s <= 0:
        return None
    return 100.0 * least_s / measured_s
