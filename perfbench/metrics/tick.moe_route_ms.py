"""Device time of the `tick` program's ops in the `mlp/route` scope path
(the expert layers' router, top-k, combine weights and the combine), per
tick execution in the traced window."""
from perfbench import scope_paths


def read(ctx):
    return scope_paths.per_execution_ms(ctx, "tick", "mlp/route")
