"""Device milliseconds a step in the operations of one program
(``params.program``, which the trace shows as ``jit_<program>``) that
lie under one of the ``jax.named_scope``s in ``params.scopes``, forward
or transposed; a fusion counts where its root operation does.  A trace
in which that program did not run (a program from before it had the
name): nothing returned.  The program there but none of its operations
under any ``mx.`` scope: an error that names the program, because that
is what a stale executable looks like (jax leaves scope names out of
its persistent cache's key: PERF.md section 7)."""
from .. import span_reduce


def read(metric, ctx):
    p = metric["params"]
    _spans, names = span_reduce.of(ctx)
    got = span_reduce.scope_seconds(ctx.trace, names, p["program"],
                                    p["scopes"])
    if got is None:
        return None
    if not span_reduce.scoped(names, p["program"]):
        raise RuntimeError(
            f"perfbench: {metric['name']} looked in jit_{p['program']} "
            f"({got[1]} executions in the window) and none of its "
            f"operations has an mx. scope in its "
            f"{span_reduce.OP_NAME_STAT}: an executable compiled before "
            f"the scopes were added, served by the compile cache?")
    ctx.note(f"{metric['name']}: {got[0] * 1e3:.4f} ms a step under "
             f"{p['scopes']} over {got[1]} executions of "
             f"jit_{p['program']}; {got[2] * 1e3:.4f} ms a step elsewhere")
    return 1e3 * got[0]
