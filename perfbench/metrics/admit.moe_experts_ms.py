"""Device time of the `admit` program's ops in the `mlp/experts` scope
path (the held routed experts' matmuls over every row of the dropless
buffer), per admit execution in the traced window."""
from perfbench import scope_paths


def read(ctx):
    return scope_paths.per_execution_ms(ctx, "admit", "mlp/experts")
