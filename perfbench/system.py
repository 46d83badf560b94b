"""The system under test: an `Engine` built the way the serve launcher
builds one, with weights drawn from the seed by `weights.py`.

The launcher's own `parser`, `model_config` and `engine_options` make
the config and the options; only the sizes a configuration file states
are passed on its command line, so every other engine option is the
launcher's default.  The weights are not the launcher's `init_params`:
the reference has to draw the same values without the program, so they
come from `weights.draw` by leaf name, in one jitted call on the device,
in the type they are served in.

A configuration file brings its own architecture with three optional
keys (PERF.md, section 4): `"program"`, `ModelConfig` fields set after
the dense sizes (experts, LoRA ranks, `layer_pattern`, ...), never one
that the dense keys set; `"leaves"`, `{program leaf suffix: [reference
leaf name, kind]}` over `_LEAVES`, the longest matching suffix winning;
and `"reference"`, the module whose `KINDS` may add weight kinds.  A
stacked leaf under `layers.pos{k}` has one row per period, and its row j
is drawn as reference layer j * len(layer_pattern) + k.
"""
from __future__ import annotations

import jax

from perfbench import correct
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
    fields = dict(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["dtype"])
    program = cfg.get("program", {})
    stated = sorted(set(program) & set(fields))
    if stated:
        raise ValueError(f"\"program\" restates {stated}, which the "
                         f"file's dense keys set")
    fields.update({k: tuple(v) if isinstance(v, list) else v
                   for k, v in program.items()})
    mc = launch.model_config(args).replace(**fields)
    if mc.page_size != cfg["engine"]["page_size"]:
        raise ValueError(f"program page size {mc.page_size} differs from "
                         f"the file's {cfg['engine']['page_size']}")
    return mc


def _path_name(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def leaf_table(cfg: dict) -> dict:
    """`_LEAVES` updated by the file's `"leaves"`."""
    return {**_LEAVES, **{k: tuple(v)
                          for k, v in cfg.get("leaves", {}).items()}}


def match(table: dict, pstr: str) -> tuple:
    """(reference name, kind) of the longest suffix of `pstr`, on a `.`
    boundary, that `table` holds."""
    keys = [k for k in table if pstr == k or pstr.endswith("." + k)]
    if not keys:
        raise ValueError(f"no seeded draw for program leaf {pstr}")
    return table[max(keys, key=len)]


def position(pstr: str) -> int:
    """k of a stacked leaf under `layers.pos{k}`: its row j is reference
    layer j * len(layer_pattern) + k."""
    return int(pstr.split(".")[1].removeprefix("pos"))


def drawer(cfg: dict):
    """`weights.draw`, with the reference module's `KINDS` looked up
    first: `KINDS[kind](leaf key, shape, dtype)`."""
    kinds = getattr(correct.reference(cfg), "KINDS", {})

    def draw(root, name, layer, shape, kind, dtype):
        if kind in kinds:
            return kinds[kind](W.leaf_key(root, name, layer), shape, dtype)
        return W.draw(root, name, layer, shape, kind, dtype)
    return draw


def make_params(cfg: dict, mc, seed: int):
    """The program's parameter tree, every leaf drawn from the seed; with
    `"weights": "int8"` every linear is a served int8 `QuantizedTensor`."""
    from repro.core.quant import QuantizedTensor
    from repro.models import model as M

    shapes = jax.eval_shape(lambda: M.init_params(mc, jax.random.PRNGKey(0)))
    int8 = cfg["weights"] == "int8"
    table, draw = leaf_table(cfg), drawer(cfg)

    def build(root):
        def leaf(path, sd):
            pstr = _path_name(path)
            name, kind = match(table, pstr)
            if int8 and kind == "matrix":
                kind = "int8"
            if pstr.startswith("layers."):
                out = W.stacked(root, name, sd.shape[0], sd.shape[1:], kind,
                                sd.dtype, draw, stride=len(mc.layer_pattern),
                                offset=position(pstr))
            else:
                out = draw(root, name, 0, sd.shape, kind, sd.dtype)
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
