"""Device time of the `tick` program's ops in the `mlp/experts` scope
path (the held routed experts' matmuls), per tick execution in the
traced window."""
from perfbench import scope_paths


def read(ctx):
    return scope_paths.per_execution_ms(ctx, "tick", "mlp/experts")
