"""Host spans of a traced window: how often each ran, its total seconds and
its self seconds (its duration less the part its child spans cover).

Spans come from ``trace.extract`` (the benchmark's, the engine's and the
trainer's, all emitted from one host thread), so nesting is containment:
a span's parent is the innermost span that holds it."""

from __future__ import annotations

from bench.harness import trace


def nest(ext: dict) -> list[tuple[str, int, int, int]]:
    """[(name, start_ns, dur_ns, parent index or -1)] of the host spans
    inside the window span (``trace.WINDOW_SPAN``, itself left out), in
    start order; an outer span comes before the spans it holds."""
    w0, w1 = trace.window_bounds(ext)
    spans = sorted(((n, s, d) for n, s, d in ext["host"]
                    if n != trace.WINDOW_SPAN and w0 <= s and s + d <= w1),
                   key=lambda h: (h[1], -h[2]))
    out, stack = [], []
    for i, (name, s, d) in enumerate(spans):
        while stack and s >= spans[stack[-1]][1] + spans[stack[-1]][2]:
            stack.pop()
        out.append((name, s, d, stack[-1] if stack else -1))
        stack.append(i)
    return out


def summarize(ext: dict) -> dict[str, dict]:
    """{span name: {"count", "total_s", "self_s"}} inside the window."""
    spans = nest(ext)
    child_ns = [0] * len(spans)
    for name, s, d, parent in spans:
        if parent >= 0:
            child_ns[parent] += d
    out: dict[str, dict] = {}
    for (name, s, d, _), kids in zip(spans, child_ns):
        o = out.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        o["count"] += 1
        o["total_s"] += d * 1e-9
        o["self_s"] += (d - kids) * 1e-9
    return out
