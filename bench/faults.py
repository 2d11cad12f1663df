#!/usr/bin/env python3
"""Faults planted under the timed path, to show that ``correct`` catches
them: each one breaks the program the way a faulty change could, and a run
with it must come out not correct.

    python3 bench/faults.py --workload <name> --fault <fault> \
        --seeds <n> <n> ... --seconds <s>

runs a cell once per seed with the fault in place, in one process, and
prints one JSON line per seed: the readings, and the verdict under the
cell's limits. ``tests/bench/test_bench_run.py`` plants the same faults at a
size the CPU can serve.

* ``state_unchanged``: the edit step returns its state as it came;
* ``half_batch``: the edit step drops the edits of the second half of the
  batch;
* ``edit_token_altered``: the edit step applies each edit with another
  token;
* ``token_altered``: the last token of every suggestion is altered;
* ``patch_negated``: the ``fused_step`` kernel subtracts each changed
  column's patch to the score totals where it should add it;
* ``patch_skipped``: the ``fused_step`` kernel leaves the score totals of
  the rows that did not change unpatched.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _engine():
    from repro.serving.batch_engine import BatchedJitEngine

    return BatchedJitEngine


def state_unchanged(setattr_):
    import jax.numpy as jnp

    def step(self, state, slot, tok, pos_id, op):
        return state, jnp.zeros((slot.shape[0],), bool)

    setattr_(_engine(), "batch_apply_edits", step)


def half_batch(setattr_):
    import jax.numpy as jnp

    real = _engine().batch_apply_edits

    def step(self, state, slot, tok, pos_id, op):
        keep = jnp.arange(slot.shape[0])[:, None] < slot.shape[0] // 2
        return real(self, state, jnp.where(keep, slot, -1), tok, pos_id, op)

    setattr_(_engine(), "batch_apply_edits", step)


def edit_token_altered(setattr_):
    real = _engine().batch_apply_edits

    def step(self, state, slot, tok, pos_id, op):
        return real(self, state, slot, (tok + 1) % self.cfg.vocab, pos_id, op)

    setattr_(_engine(), "batch_apply_edits", step)


def token_altered(setattr_):
    from repro.serving.suggest import SuggestionEngine

    real = SuggestionEngine.refresh

    def refresh(self, *args, **kwargs):
        out = real(self, *args, **kwargs).copy()
        out[-1] = (out[-1] + 1) % self.cfg.vocab
        return out

    setattr_(SuggestionEngine, "refresh", refresh)


def _kernel(setattr_, wrap):
    import repro.kernels.fused_step as fs

    setattr_(fs, "fused_patch_assign", wrap(fs.fused_patch_assign))


def patch_negated(setattr_):
    def wrap(real):
        def kernel(q, k_new, k_old, vc_new, vc_old, *rest, **kw):
            return real(q, k_old, k_new, vc_old, vc_new, *rest, **kw)
        return kernel

    _kernel(setattr_, wrap)


def patch_skipped(setattr_):
    def wrap(real):
        def kernel(q, k_new, k_old, vc_new, vc_old, mask, *rest, **kw):
            return real(q, k_new, k_old, vc_new, vc_old, mask * 0.0, *rest,
                        **kw)
        return kernel

    _kernel(setattr_, wrap)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  edit_token_altered, token_altered,
                                  patch_negated, patch_skipped)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import time

    import run

    import program

    cell = run.resolve(args.workload)
    FAULTS[args.fault](setattr)
    # a reading needs no warm-up: the broken programs compile as the window
    # reaches them
    program.warm_shapes = lambda *a, **k: None
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False,
                           t_start=time.perf_counter())
        print(json.dumps(run.finite({
            "fault": args.fault, "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "checks": res["checks"],
            "readings": res["info"]["readings"]})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
