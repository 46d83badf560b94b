"""The system under test: an `Engine` built the way the serve launcher
builds one, with weights drawn from the seed by `weights.py`.

The launcher's own `parser`, `model_config` and `engine_options` make
the config and the options; only the sizes a configuration file states
are passed on its command line, so every other engine option is the
launcher's default.  The weights are not the launcher's `init_params`:
the reference has to draw the same values without the program, so they
come from `weights.draw` by leaf name, in one jitted call on the device,
in the type they are served in.
"""
from __future__ import annotations

import jax

from perfbench import weights as W

# program tree leaf name -> (reference leaf name, weight kind)
_LEAVES = {
    "embedding": ("embedding", "embed"), "unembed": ("unembed", "matrix"),
    "final_norm.scale": ("final_norm", "norm"),
    "norm1.scale": ("attn_norm", "norm"), "norm2.scale": ("mlp_norm", "norm"),
    "wq": ("wq", "matrix"), "wk": ("wk", "matrix"), "wv": ("wv", "matrix"),
    "wo": ("wo", "matrix"), "w_gate": ("w_gate", "matrix"),
    "w_up": ("w_up", "matrix"), "w_down": ("w_down", "matrix"),
}


def launcher_args(cfg: dict, extra: tuple = ()):
    from repro.launch import serve as launch
    eng = cfg["engine"]
    argv = ["--arch", cfg["arch"], "--slots", str(eng["slots"]),
            "--max-seq", str(eng["max_seq"]),
            "--num-pages", str(eng["num_pages"]),
            *cfg.get("launcher_args", ()), *extra]
    return launch.parser().parse_args(argv)


def model_config(cfg: dict, args):
    """The launcher's ModelConfig with the sizes the file states: the file
    holds the configuration as it is run."""
    from repro.launch import serve as launch
    mc = launch.model_config(args).replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["dtype"])
    if mc.page_size != cfg["engine"]["page_size"]:
        raise ValueError(f"program page size {mc.page_size} differs from "
                         f"the file's {cfg['engine']['page_size']}")
    return mc


def _path_name(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def make_params(cfg: dict, mc, seed: int):
    """The program's parameter tree, every leaf drawn from the seed; with
    `"weights": "int8"` every linear is a served int8 `QuantizedTensor`."""
    from repro.core.quant import QuantizedTensor
    from repro.models import model as M

    shapes = jax.eval_shape(lambda: M.init_params(mc, jax.random.PRNGKey(0)))
    int8 = cfg["weights"] == "int8"

    def build(root):
        def leaf(path, sd):
            pstr = _path_name(path)
            key = next((k for k in _LEAVES if pstr.endswith(k)), None)
            if key is None:
                raise ValueError(f"no seeded draw for program leaf {pstr}")
            name, kind = _LEAVES[key]
            if int8 and kind == "matrix":
                kind = "int8"
            if pstr.startswith("layers."):
                out = W.stacked(root, name, mc.num_layers, sd.shape[1:],
                                kind, sd.dtype)
            else:
                out = W.draw(root, name, 0, sd.shape, kind, sd.dtype)
            if kind == "int8":
                return QuantizedTensor(*out, 8, False, tuple(sd.shape))
            return out
        return jax.tree_util.tree_map_with_path(leaf, shapes)

    return jax.jit(build)(W.root_key(seed))


def build_engine(cfg: dict, seed: int):
    """(engine, params, ModelConfig) for one configuration file."""
    from repro.launch import serve as launch
    from repro.runtime.serve import Engine

    args = launcher_args(cfg)
    mc = model_config(cfg, args)
    params = make_params(cfg, mc, seed)
    engine = Engine(mc, params, options=launch.engine_options(args))
    return engine, params, mc
