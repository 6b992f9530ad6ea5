"""Fake-quant: share of the train program's device time spent in ops
traced under ``quant/`` (the weight and activation fake-quant of
``repro.core.quantization``, their backward included), joined from the
trace's instructions to the ``op_name`` of the compiled step's text.  Ops
with no scope stay in the denominator; their share is logged.  Moves
``train_tok_s``."""

import sys

from bench.harness import scopes


def read(rec: dict):
    ops = ((rec.get("trace") or {}).get("ops_by_kind") or {}).get("train")
    if rec.get("kind") != "train" or not ops:
        return None
    names = scopes.op_scopes(scopes.train_step_text(rec["cfg"], rec["mix"]))
    got = scopes.share(ops, names, "quant/")
    if got is None:
        return None
    print(f"[bench] fake_quant_share.train: {got[1]:.2f}% of the step's "
          "device time has no scope", file=sys.stderr)
    return got[0]
