"""Peaks of the chip, and the work an edit dispatch cannot do without.

Counts are floors, taken from each dispatch's real sizes: the documents
that took edits, their real lengths ``n`` and the edits by kind. Padded
rows, padded slots, filler documents, the row buckets an overflow
re-ingests and the copies the program makes today are never counted, so a
faster implementation of the same algorithm can approach these numbers but
not pass them. What one document's edits cost is the architecture's, and
its module (``bench/models/``) counts it: ``kernel_flops``, ``kernel_bytes``
and ``edit_flops``.
"""
from __future__ import annotations

# Per-chip peaks keyed by ``jax.Device.device_kind``. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
# at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """A device the table does not know is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def dispatch_totals(module, model: dict, dispatches: list) -> dict:
    """Sums of ``module``'s counts over dispatches, each a list of (n,
    replaced, inserted, deleted) for the documents that took edits."""
    out = {"edit_flops": 0.0, "kernel_flops": 0.0, "kernel_bytes": 0.0,
           "dispatches": len(dispatches)}
    for docs in dispatches:
        for n, r, i, dl in docs:
            out["edit_flops"] += module.edit_flops(model, n, r, i, dl)
            out["kernel_flops"] += module.kernel_flops(model, n, r, i, dl)
            out["kernel_bytes"] += module.kernel_bytes(model, n, r, i, dl)
    return out
