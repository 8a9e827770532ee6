"""What the harness knows of the program: how to build its
``ModelConfig`` and parameter tree from what a configuration's reference
module (``bench/references/<reference>.py``) says of them.  The
architecture itself is the reference module's: its ``program_fields``
and ``program_paths``.  Everything else about the program is reached
through ``repro.train.trainer.train``."""
from __future__ import annotations

import dataclasses
import typing
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def _nested(cls, name: str, value):
    """A dict given for a field typed ``Optional[<dataclass>]`` built into
    that dataclass; any other value as it is."""
    if not isinstance(value, dict):
        return value
    hint = typing.get_type_hints(cls)[name]
    kinds = [t for t in (typing.get_args(hint) or (hint,))
             if dataclasses.is_dataclass(t)]
    if len(kinds) != 1:
        raise TypeError(f"{cls.__name__}.{name} takes no nested fields")
    return kinds[0](**value)


def model_config(c: Dict, ref):
    """The program's ModelConfig for configuration file ``c``: the
    registry entry ``registry_arch``, renamed to the file's name, with
    every field that ``ref.program_fields(c)`` sets."""
    from repro.configs import get_config
    base = get_config(c["registry_arch"])
    fields = {k: _nested(type(base), k, v)
              for k, v in ref.program_fields(c).items()}
    return dataclasses.replace(base, arch_id=c["name"], **fields)


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _tuples(node):
    """Nested dicts whose keys are the slot indices 0..n-1 -> tuples."""
    if not isinstance(node, dict):
        return node
    out = {k: _tuples(v) for k, v in node.items()}
    if out and all(isinstance(k, int) for k in out):
        if sorted(out) != list(range(len(out))):
            raise ValueError(f"slots {sorted(out)} are not 0..n-1")
        return tuple(out[i] for i in range(len(out)))
    return out


def to_program(w: Dict[str, jnp.ndarray], dtype,
               paths: Dict[str, Tuple]) -> Dict:
    """Reference tensors -> the program's parameter tree in ``dtype``,
    each at its path in ``paths``; an integer in a path is a slot of a
    tuple (one per slot of the layer pattern)."""
    out: Dict = {}
    for name, x in w.items():
        path = paths[name]
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x.astype(dtype)
    return _tuples(out)


def from_program(params, paths: Dict[str, Tuple]) -> Dict[str, jnp.ndarray]:
    """The program's parameter tree -> reference tensor names."""
    out = {}
    for name, path in paths.items():
        try:
            out[name] = _get(params, path)
        except (KeyError, IndexError, TypeError):
            continue
    return out


def check_layout(mc, shapes: Dict[str, tuple],
                 paths: Dict[str, Tuple]) -> None:
    """Fail before anything runs if the program's parameter tree is not
    the one ``paths`` maps the reference onto."""
    from repro.models import model as M
    want = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), mc))
    have = jax.eval_shape(lambda: to_program(
        {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()},
        mc.pdtype, paths))
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise RuntimeError(
            "the program's parameter tree no longer matches the "
            f"reference layout:\n program {want}\n reference {have}")
