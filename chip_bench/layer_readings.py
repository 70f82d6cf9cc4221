"""Readings shared by the per-layer metric readers in ``metrics/``. Each
returns None where the run holds nothing to read."""
from __future__ import annotations


def per_batch(ctx: dict, key: str) -> float | None:
    return ctx[key] / ctx["batches"] if ctx["batches"] else None


def device_ms_per(ctx: dict, key: str) -> float | None:
    trace = ctx["trace"]
    if trace is None or not ctx[key]:
        return None
    return trace["busy_s"] * 1e3 / ctx[key]


def idle_share(ctx: dict) -> float | None:
    trace = ctx["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]


def span_ms_per_batch(ctx: dict, part: str) -> float | None:
    """``program_spans.per_batch_ms``'s reading ``part`` of the traced
    window: milliseconds a batch the program's spans took."""
    trace = ctx["trace"]
    return None if trace is None else trace["per_batch_ms"].get(part)


def window_delta(ctx: dict, counter: str) -> float | None:
    """A program counter's rise over the window; None where the program
    does not have it."""
    program = ctx["program"]
    if counter not in program["open"] or counter not in program["close"]:
        return None
    return program["close"][counter] - program["open"][counter]
