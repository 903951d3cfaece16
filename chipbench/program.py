"""The system under test, driven through its public entry only, and the
readings the check takes from its state.

``build`` makes the engine the way a user of the library does:
``repro.engine.create("l2l-p", cfg, make_exec_cfg(shape, cfg, mesh),
optimizer=adam(lr))`` with the production relay settings.  The readings
name each leaf as the reference does: ``embed.tok``, ``head.ln_f.scale``,
``layers.<i>.attn.wq`` (one entry per row of a stacked layer array).
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from chipbench.reference import model as M

ROOT = Path(__file__).resolve().parents[1]

# what the configuration file says, against the program's field for it
CONFIG_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "qkv_bias",
    "compute_dtype": "dtype", "param_dtype": "param_dtype",
}


def import_program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro import engine
    from repro.configs.base import InputShape, get_config
    from repro.launch.build import make_exec_cfg
    from repro.optim import adam
    return engine, InputShape, get_config, make_exec_cfg, adam


def program_config(conf: dict):
    """The program's configuration for a configuration file, checked
    against the file's keys: a mismatch is an error, not a warning."""
    _, _, get_config, _, _ = import_program()
    p = conf["program"]
    cfg = get_config(p["arch"], p["variant"]).replace(**p["replace"])
    c = conf["config"]
    bad = [(k, c[k], getattr(cfg, f)) for k, f in CONFIG_FIELDS.items()
           if c[k] != getattr(cfg, f)]
    eps = c.get("layer_norm_eps", c.get("rms_norm_eps"))
    if eps != cfg.norm_eps or c["norm"] != cfg.norm_type:
        bad.append(("norm", (c["norm"], eps), (cfg.norm_type, cfg.norm_eps)))
    if (c["hidden_act"] == "silu") != cfg.gated_mlp:
        bad.append(("hidden_act", c["hidden_act"], cfg.act))
    if bad:
        raise SystemExit(f"chipbench: the program's configuration departs "
                         f"from {conf['name']}'s file: {bad}")
    return cfg


def build(conf: dict, cfg, traffic: dict):
    """The l2l-p engine with the production settings for this shape."""
    engine, InputShape, _, make_exec_cfg, adam = import_program()
    shape = InputShape(f"b{traffic['batch']}-s{traffic['seq']}",
                       traffic["seq"], traffic["batch"], "train")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    o = conf["optimizer"]
    return engine.create("l2l-p", cfg, make_exec_cfg(shape, cfg, mesh),
                         optimizer=adam(o["lr"], o["b1"], o["b2"], o["eps"]))


def _name(path) -> tuple:
    parts = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    return tuple(parts)


def _on_device(a):
    return jax.device_put(a, SingleDeviceSharding(jax.devices()[0]))


_rownorms = jax.jit(lambda a: jnp.sqrt(jnp.sum(
    jnp.square(a.reshape(a.shape[0], -1)), axis=1)))
_norm = jax.jit(jnp.linalg.norm)


def _readings(tree, per_leaf, slot=None) -> dict:
    """name -> norm of every leaf of a params-shaped tree; ``per_leaf``
    maps (path, device array) to the norm (or per-row norms)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = _name(path)
        if slot is not None:
            if parts[-1] != slot:
                continue
            parts = parts[:-1]
        value = per_leaf(".".join(parts), _on_device(leaf))
        if parts[0] == "groups":
            rest = ".".join(parts[2:])
            for i, x in enumerate(np.asarray(value)):
                out[f"layers.{i}.{rest}"] = float(x)
        else:
            out[".".join(parts)] = float(value)
    return out


def load_weights(state, conf: dict, key):
    """The state with the run's weights (``reference.model.init_params``,
    made on the device in one jitted call) in place of the ones
    ``Engine.init`` drew, each array where the engine keeps it."""
    fresh = jax.jit(functools.partial(M.init_params, conf["config"]))(key)
    if jax.tree.structure(fresh) != jax.tree.structure(state.params):
        raise SystemExit("chipbench: the program's parameters are not laid "
                         "out as the reference's")
    return state.replace(params=jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        fresh, state.params))


def grad_readings(state, b1: float) -> dict:
    """Norms of the first gradient as the optimizer got it, from Adam's
    first moment after one step (m = (1 - b1) g)."""
    def per_leaf(path, m):
        stacked = path.startswith("groups")
        return (_rownorms(m) if stacked else _norm(m)) / (1.0 - b1)
    return _readings(state.opt_state, per_leaf, slot="m")


def change_readings(state, conf: dict, key) -> dict:
    """Norms of each leaf's change from the run's weights (made again)."""
    init = jax.jit(functools.partial(M.init_params, conf["config"]))(key)
    flat = {".".join(_name(p)): a for p, a in
            jax.tree_util.tree_flatten_with_path(init)[0]}
    diff = jax.jit(lambda a, b: a - b)

    def per_leaf(path, p):
        d = diff(p, flat[path])
        return _rownorms(d) if path.startswith("groups") else _norm(d)
    return _readings(state.params, per_leaf)
