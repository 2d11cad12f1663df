"""Ambient sharding context.

Model code calls ``constrain(x, "batch", None, "model")`` with *logical* axis
names; when a ShardingCtx is active these become with_sharding_constraint
calls on the production mesh, and when no context is set (unit tests, eager
CPU runs) they are no-ops. This keeps the model definitions mesh-agnostic.

Logical axes:
  batch  -> all data-parallel mesh axes ("pod", "data") when present
  seq    -> "data" (context/sequence parallelism, used for long-context decode)
  model  -> "model" (tensor parallelism: heads, ffn hidden, vocab, experts)
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


@dataclass
class ShardingCtx:
    mesh: Mesh

    def resolve(self, logical: Optional[str]):
        if logical is None:
            return None
        names = self.mesh.axis_names
        if logical == "batch":
            axes = tuple(a for a in ("pod", "data") if a in names)
            return axes if axes else None
        if logical == "seq":
            return "data" if "data" in names else None
        if logical == "model":
            return "model" if "model" in names else None
        if logical == "seq_model":
            # context parallelism ON the tensor axis: used when head counts
            # don't divide the model axis (hymba 25H, phi4 24H, internvl 14H)
            # so head sharding would silently replicate (§Perf iteration 3)
            return "model" if "model" in names else None
        raise ValueError(f"unknown logical axis {logical}")

    def spec(self, *logical_axes, dims: Optional[tuple] = None) -> P:
        """Resolve logical axes with two safeguards: a mesh axis may appear
        only once per spec (first use wins — batch=1 decode wants both
        "batch" and "seq" on "data"); and when ``dims`` is given, axes whose
        dimension does not divide the mesh-axis size resolve to None (so a
        batch-1 tensor never claims the data axis and the seq axis can)."""
        used: set = set()
        out = []
        for i, a in enumerate(logical_axes):
            r = self.resolve(a)
            flat = r if isinstance(r, tuple) else (r,)
            if r is not None and dims is not None:
                size = 1
                for f in flat:
                    size *= self.mesh.shape[f]
                if dims[i] % size != 0:
                    r = None
            if r is None or any(f in used for f in flat):
                out.append(None)
            else:
                used.update(flat)
                out.append(r)
        return P(*out)


def set_ctx(ctx: Optional[ShardingCtx]) -> None:
    _state.ctx = ctx


def get_ctx() -> Optional[ShardingCtx]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = get_ctx()
    set_ctx(ShardingCtx(mesh))
    try:
        yield get_ctx()
    finally:
        set_ctx(prev)


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """Apply a sharding constraint using logical axis names (no-op w/o ctx)."""
    ctx = get_ctx()
    if ctx is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"constrain: {len(logical_axes)} axes for rank-{x.ndim}")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, ctx.spec(*logical_axes, dims=x.shape))
    )
