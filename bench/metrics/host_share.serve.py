"""Scheduler (the engine's host side): share of the traced window in which
the chip sat idle while the engine's own host code ran, read from the
trace's idle-gap breakdown: gaps whose innermost host span is a phase of
``serve/step`` other than its waits (the blocking ``serve/*_fetch`` and
``serve/wait_arrival``).  The breakdown keeps its 10 longest spans and
leaves out gaps under 10 us.  Silent where the engine has no step spans.
Moves ``tpot_p90_ms``."""

# the engine's host phases; serve/decode_chunk and serve/chunked_prefill
# count their own host work, their fetches being spans of their own
HOST_PHASES = ("serve/step", "serve/admit", "serve/admission_prefill",
               "serve/prefix_cow", "serve/chunked_prefill",
               "serve/ensure_blocks", "serve/decode_chunk",
               "serve/decode_dispatch", "serve/process_chunk")
# spans only an engine with a step span tree emits
STEP_SPANS = ("serve/step", "serve/admit", "serve/ensure_blocks",
              "serve/decode_dispatch", "serve/decode_fetch",
              "serve/prefill_fetch", "serve/process_chunk",
              "serve/wait_arrival")


def read(rec: dict):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    gaps = dict(tr["idle_gaps"])
    if not any(name in gaps for name in STEP_SPANS):
        return None
    return 100.0 * sum(gaps.get(n, 0.0) for n in HOST_PHASES) / tr["window_s"]
