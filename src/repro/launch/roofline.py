"""Analytic roofline model for the incremental edit step (§Roofline).

``launch/dryrun.py`` rooflines the *training* stack compositionally from
lowered stage bodies; serving needs the same discipline for the edit hot
path. This module prices what the incremental algorithm *must* do for one
``(B, n_cap, C, R)`` bucketed step — the useful work — so the gated
``benchmarks/hot_path.py`` can report how much of each compiled step's
XLA-measured FLOPs/bytes is algorithmically necessary vs padding + plumbing
(``achieved vs roofline``), and CI can hold the fraction.

The model counts only the matmul-shaped terms (projections, score dots,
value accumulations); elementwise work (gelu, masks, argmax) is O(of the
same shapes) and under the constant-factor noise floor of a roofline.

All functions are pure shape arithmetic — no jax imports, no tracing — so
the hot-path bench can price shapes it never compiles.
"""
from __future__ import annotations

from dataclasses import dataclass

# Per-chip peaks keyed by ``jax.Device.device_kind``, the one table every
# roofline in the repo reads. Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect over 4 links = 50 GB/s per link).
CHIP_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s_per_link": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``. A device the table does not
    know is an error, never a default."""
    if device_kind not in CHIP_PEAKS:
        raise ValueError(f"no peak figures for device kind {device_kind!r}; "
                         f"known: {sorted(CHIP_PEAKS)}")
    return CHIP_PEAKS[device_kind]


def edit_step_flops(n_layers: int, meta: dict, n_cap: int, C: int,
                    R: int, d_ff: int = 0) -> float:
    """Useful FLOPs of ONE document's bucketed ``apply_edits`` step.

    Per layer the incremental algorithm (paper §3.2, DESIGN.md §3) does:

    * edited-slot refresh: C slots re-embed and re-project to q/k/v
      (2·C·d·3·H·dh) and re-code through the VQ value path (2·C·H·dh·Q);
    * column patch: every row's totals gain the new-minus-old contribution
      of the C patched columns — scores (2 dots) 2·2·n·C·H·dh and value
      accumulations 2·2·n·C·H·Q;
    * dirty-row recompute: R rows re-run full attention over n columns —
      scores 2·R·n·H·dh, accumulation 2·R·n·H·Q — then the row MLP
      (2·R·d·d_ff·2; ``d_ff`` defaults to 4·d when not given) and output
      projection (2·R·H·dh·d).

    ``n_cap`` stands in for ``n`` (the compiled step cannot see n_real):
    this IS the padding honesty of the model — a half-full capacity class
    doubles the reported roofline relative to its truly-useful work, and
    the achieved fraction says so.
    """
    d, H, dh, Q = meta["d"], meta["H"], meta["dh"], meta["Q"]
    d_ff = d_ff or 4 * d
    n = n_cap
    per_layer = (
        2 * C * d * 3 * H * dh        # edited-slot qkv projection
        + 2 * C * H * dh * Q          # edited-slot VQ value coding
        + 2 * 2 * n * C * H * dh      # patch scores (old + new columns)
        + 2 * 2 * n * C * H * Q       # patch value accumulation (old + new)
        + 2 * R * n * H * dh          # dirty-row scores
        + 2 * R * n * H * Q           # dirty-row value accumulation
        + 2 * R * d * d_ff * 2        # dirty-row MLP (in + out mats)
        + 2 * R * H * dh * d          # dirty-row output projection
    )
    return float(n_layers) * per_layer


def edit_step_bytes(n_layers: int, meta: dict, n_cap: int,
                    weight_bytes: int = 0) -> float:
    """Minimum HBM traffic of one step: read + write the document state
    (every leaf is gathered/scattered at least once by the patch) plus one
    read of the weight stacks (``weight_bytes``; pass the engine's real
    number, 0 to price state traffic alone)."""
    from repro.serving.jit_engine import state_nbytes_for

    return 2.0 * state_nbytes_for(n_cap, n_layers, meta) + float(weight_bytes)


@dataclass
class RooflineReport:
    """Analytic floor vs XLA-measured cost of one compiled step."""
    analytic_flops: float
    analytic_bytes: float
    xla_flops: float
    xla_bytes: float
    peak_flops: float
    hbm_bw: float

    @property
    def compute_s(self) -> float:
        return self.analytic_flops / self.peak_flops

    @property
    def memory_s(self) -> float:
        return self.analytic_bytes / self.hbm_bw

    @property
    def bottleneck(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"

    @property
    def useful_flop_fraction(self) -> float:
        """analytic / XLA-counted FLOPs: how much of the compiled step is
        algorithmically necessary (1.0 = no waste; small = the step spends
        its arithmetic on padding or redundant recompute)."""
        return self.analytic_flops / self.xla_flops if self.xla_flops else 0.0

    @property
    def useful_byte_fraction(self) -> float:
        return self.analytic_bytes / self.xla_bytes if self.xla_bytes else 0.0

    def summary(self) -> dict:
        return {
            "analytic_flops": self.analytic_flops,
            "analytic_bytes": self.analytic_bytes,
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
            "useful_flop_fraction": round(self.useful_flop_fraction, 6),
            "useful_byte_fraction": round(self.useful_byte_fraction, 6),
            "bottleneck": self.bottleneck,
        }


def edit_step_roofline(n_layers: int, meta: dict, n_cap: int, C: int, R: int,
                       *, xla_flops: float, xla_bytes: float,
                       device_kind: str, weight_bytes: int = 0,
                       batch: int = 1, d_ff: int = 0) -> RooflineReport:
    """Price a ``(B, n_cap, C, R)`` batched edit step against its analytic
    floor on a ``device_kind`` chip. ``xla_flops``/``xla_bytes`` come from
    the compiled step's ``cost_analysis()`` (whole-batch numbers); the
    analytic side scales the per-document model by ``batch`` and charges
    the weights once (they are shared across the batch)."""
    peaks = chip_peaks(device_kind)
    return RooflineReport(
        analytic_flops=batch * edit_step_flops(n_layers, meta, n_cap, C, R,
                                               d_ff=d_ff),
        analytic_bytes=(batch * edit_step_bytes(n_layers, meta, n_cap)
                        + float(weight_bytes)),
        xla_flops=float(xla_flops), xla_bytes=float(xla_bytes),
        peak_flops=peaks["flops"], hbm_bw=peaks["hbm_bytes_per_s"],
    )
