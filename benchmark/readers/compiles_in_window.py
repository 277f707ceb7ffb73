"""Compilations inside the window: the larger of JAX's own log lines
("Compiling <program> ...", JAX_LOG_COMPILES=1 in a traced run's server)
written between the window's start and its end, and the compile spans
the trace holds.  Expected 0: set-up warms every shape."""


def read(ctx: dict) -> float | None:
    if ctx.get("compile_log_lines") is None:
        return None
    trace = ctx.get("trace") or {}
    return float(max(ctx["compile_log_lines"],
                     trace.get("compile_events", 0)))
