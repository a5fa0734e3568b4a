"""Seconds of set-up's first warm plan call spent in JAX's trace, lower,
XLA compile and persistent-cache load (the `jit_s` of that call's spans in
the program's span log)."""
import phases


def read(ctx):
    jit = phases.first_call_jit()
    return None if jit is None else sum(sum(p.values()) for p in jit.values())
