"""What the harness knows of the program: how a configuration file maps
onto its ``ModelConfig``, and where each reference tensor lives in its
parameter tree.  Everything else about the program is reached through
``repro.train.trainer.train``."""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# reference tensor name -> path in the program's parameter tree (one
# pattern slot of global layers, stacked on a leading layer axis)
PATHS: Dict[str, Tuple] = {
    "embed": ("embed", "embed"),
    "unembed": ("unembed", "unembed"),
    "final_norm": ("final_norm", "scale"),
    "norm1": ("blocks", 0, "norm1", "scale"),
    "wq": ("blocks", 0, "attn", "wq"),
    "wk": ("blocks", 0, "attn", "wk"),
    "wv": ("blocks", 0, "attn", "wv"),
    "wo": ("blocks", 0, "attn", "wo"),
    "norm2": ("blocks", 0, "norm2", "scale"),
    "w_gate": ("blocks", 0, "ffn", "w_gate"),
    "w_up": ("blocks", 0, "ffn", "w_up"),
    "w_down": ("blocks", 0, "ffn", "w_down"),
}


def model_config(c: Dict):
    """The program's ModelConfig for configuration file ``c``: the
    registry entry ``registry_arch`` with every size set from the file."""
    from repro.configs import get_config
    base = get_config(c["registry_arch"])
    return dataclasses.replace(
        base, arch_id=c["name"], n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        layer_pattern=("global",), activation="silu", gated_mlp=True,
        norm="rmsnorm", post_norms=False, scale_embed=False, qk_norm=False,
        use_rope=True, attn_logit_softcap=0.0, final_logit_softcap=0.0,
        moe=None, ssm=None, rglru=None, encoder=None,
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"])


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def to_program(w: Dict[str, jnp.ndarray], dtype) -> Dict:
    """Reference tensors -> the program's parameter tree in ``dtype``."""
    out: Dict = {"blocks": ({},)}
    for name, x in w.items():
        path = PATHS[name]
        node = out
        for p in path[:-1]:
            if isinstance(p, int):
                node = node[p]
            else:
                node = node.setdefault(p, {})
        node[path[-1]] = x.astype(dtype)
    return out


def from_program(params) -> Dict[str, jnp.ndarray]:
    """The program's parameter tree -> reference tensor names."""
    out = {}
    for name, path in PATHS.items():
        try:
            out[name] = _get(params, path)
        except (KeyError, IndexError, TypeError):
            continue
    return out


def check_layout(mc, shapes: Dict[str, tuple]) -> None:
    """Fail before anything runs if the program's parameter tree is not
    the one ``PATHS`` maps the reference onto."""
    from repro.models import model as M
    want = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), mc))
    have = jax.eval_shape(lambda: to_program(
        {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()},
        mc.pdtype))
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise RuntimeError(
            "the program's parameter tree no longer matches the "
            f"reference layout:\n program {want}\n reference {have}")
