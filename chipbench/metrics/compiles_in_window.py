"""Backend compiles inside the measured window, from JAX's
``/jax/core/compile/backend_compile_duration`` events: 0 when the
warm-up covered every shape the traffic uses."""
UNIT = "count"
LAYER = "stage compute (serving/gtrac_serve.stage_step)"
MOVES = "tokens_per_s"


def read(ctx):
    return float(ctx.compiles_in_window)
