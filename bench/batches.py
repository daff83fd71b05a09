"""Per-release figures over the whole batches of a traced window.

The server's worker serves one drained batch at a time: a drain ends every
``serve.queue_wait`` span of the batch, and the next drain comes only after
the batch's last reconstruction.  So the spans between the window's first
and last drains are whole batches, each fused measurement with every
reconstruction it fed, and a figure per release over them has no error
where the window's ends cut a batch.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence


def whole_batches(ctx) -> List[dict]:
    """The window's spans that lie between its first and last drains."""
    drains = sorted({s["t1"] for s in ctx.named("serve.queue_wait",
                                                  ctx.all_spans)
                     if ctx.t_open <= s["t1"] <= ctx.t_close})
    if len(drains) < 2:
        return []
    lo, hi = drains[0], drains[-1]
    return [s for s in ctx.spans if lo <= s["t0"] and s["t1"] <= hi]


def per_release(ctx, names: Sequence[str], value: Callable[[dict], float],
                present: Optional[Sequence[str]] = None) -> Optional[float]:
    """Σ ``value`` over the spans named ``names``, per ``engine.reconstruct``
    span (each release reconstructs once), over whole batches.  ``None``
    where no span named ``present`` (default: ``names``) was recorded: the
    program does not record them."""
    spans = whole_batches(ctx)
    releases = sum(1 for s in spans if s["name"] == "engine.reconstruct")
    if not releases or not any(s["name"] in (present or names)
                               for s in spans):
        return None
    return sum(value(s) for s in spans if s["name"] in names) / releases


def seconds(span: dict) -> float:
    return span["t1"] - span["t0"]


def megabytes(span: dict) -> float:
    return span["attrs"]["bytes"] / 1e6
