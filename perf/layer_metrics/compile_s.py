"""``compile_s`` — seconds JAX spent tracing, lowering and compiling (or
loading from the persistent cache) before the window opened, summed over
``jax.monitoring``'s ``/jax/core/compile/*`` durations in this process.
The program's own ``progcache.program_costs()`` is printed beside it on the
``progcache`` line: it misses programs whose trainer has no
``jit_signature()`` (the LM's step), which bypass that cache."""
LAYER = "program cache"
UNIT = "s"
SOURCE = "program_span"


def read(obs):
    return obs.get("compile_s")
