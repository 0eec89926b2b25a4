"""Entry layer: what set-up had to compile."""


def compile_misses(ctx, record, trace):
    """Persistent-cache misses before the window (``jax.monitoring``): a warm
    run reads 0; a cold one, the number of programs it compiled."""
    return record["compile_misses"]


METRICS = {"compile_misses": compile_misses}
