"""Block call: compilations inside the window.  The program's
``gdm_compile_events`` counter (a first call at a new bucket) plus the
compilations and compile-cache loads JAX reported while the window ran."""


def read(ctx):
    return ctx.counter_delta("gdm_compile_events") + ctx.monitored_compiles
