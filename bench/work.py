"""Peaks of the chip, and the work an edit dispatch cannot do without.

Counts are floors, taken from each dispatch's real sizes: the documents
that took edits, their real lengths ``n`` and the edits by kind. Padded
rows, padded slots, filler documents, the row buckets an overflow
re-ingests and the copies the program makes today are never counted, so a
faster implementation of the same algorithm can approach these numbers but
not pass them.

Per layer of one document, with ``c_new`` replaced or inserted tokens and
``c_del`` deleted ones (``d`` width, ``H`` heads of ``dh``, ``Q`` codes per
VQ head, ``F`` MLP width):

* every edited token's row changes at every layer (its residual stream
  carries the new embedding), so ``c_new`` rows are recomputed: q/k/v
  projections ``2 d 3 H dh``, value-codebook products ``2 H dh Q``, their
  attention over the ``n`` real columns ``2 n H dh + 2 n H Q``, and the MLP
  ``2 * 2 d F``;
* every other real row's accumulated scores take the edited columns: a
  replaced token's column is subtracted and added (2 columns), an inserted
  one added (1), a deleted one subtracted (1), each ``2 n H dh`` for the
  scores and ``2 n H Q`` for the value accumulation.

The ``fused_step`` kernel is the column patch and the re-quantisation: its
floor is the column terms, and its bytes are the ``q`` rows it must read,
the score totals ``T`` it reads and writes, and the codes it writes, over
the ``n`` real rows, per layer. Rows that an edit's codes propagate to are
not counted (the device alone knows them), so these are floors.
"""
from __future__ import annotations

F32 = 4

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


def _dims(model: dict):
    d, H = model["d_model"], model["n_heads"]
    return d, H, d // H, model["codebook_size"], model["d_ff"], \
        model["n_layers"]


def columns(n_repl: int, n_ins: int, n_del: int) -> int:
    """Column passes of the patch: a replace subtracts and adds."""
    return 2 * n_repl + n_ins + n_del


def kernel_flops(model: dict, n: int, n_repl: int, n_ins: int,
                 n_del: int) -> float:
    d, H, dh, Q, F, L = _dims(model)
    return float(L * columns(n_repl, n_ins, n_del)
                 * (2 * n * H * dh + 2 * n * H * Q))


def kernel_bytes(model: dict, n: int, n_repl: int, n_ins: int,
                 n_del: int) -> float:
    d, H, dh, Q, F, L = _dims(model)
    if columns(n_repl, n_ins, n_del) == 0:
        return 0.0
    hq = model["vq_heads"]
    return float(L * n * (H * dh * F32          # q rows read
                          + 2 * H * Q * F32     # T read and written
                          + hq * 4))            # codes written


def edit_flops(model: dict, n: int, n_repl: int, n_ins: int,
               n_del: int) -> float:
    """Floor of one document's edit step: row recomputes plus the patch."""
    d, H, dh, Q, F, L = _dims(model)
    c_new = n_repl + n_ins
    rows = c_new * (2 * d * 3 * H * dh + 2 * H * dh * Q
                    + 2 * n * H * dh + 2 * n * H * Q + 2 * 2 * d * F)
    return float(L * rows) + kernel_flops(model, n, n_repl, n_ins, n_del)


def dispatch_totals(model: dict, dispatches: list) -> dict:
    """Sums over dispatches, each a list of (n, replaced, inserted, deleted)
    for the documents that took edits."""
    out = {"edit_flops": 0.0, "kernel_flops": 0.0, "kernel_bytes": 0.0,
           "dispatches": len(dispatches)}
    for docs in dispatches:
        for n, r, i, dl in docs:
            out["edit_flops"] += edit_flops(model, n, r, i, dl)
            out["kernel_flops"] += kernel_flops(model, n, r, i, dl)
            out["kernel_bytes"] += kernel_bytes(model, n, r, i, dl)
    return out
