"""Plain float32 reference of a decoder whose layers alternate between a
sliding window and full attention (the program's ``layer_pattern``
("local", "global")): the dense decoder of ``decoder.py``, where query i
of a local layer sees key j only if position(i) - position(j) <
``sliding_window``, as the program's attention mask has it.  Only the
global layers send their attention to the CA servers; the local ones
run on the program's flash path beside them.

It brings its architecture to the harness as new files only (see the
interface in ``decoder.py``).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import numpy as np

from harness import flops
from harness.compare import reference_module

D = reference_module({"reference": "decoder"})
PATTERN = ("local", "global")
round_to, lr_at = D.round_to, D.lr_at
DECAYED = ("embed", "unembed") + tuple(f"{kind}.{k}" for kind in PATTERN
                                       for k in D.LAYER)


def program_fields(c: Dict) -> Dict:
    return dict(D.program_fields(c), layer_pattern=PATTERN,
                window=c["sliding_window"])


def program_paths(c: Dict) -> Dict[str, tuple]:
    """One pattern slot per kind, each stacked on a leading axis of
    ``num_hidden_layers / 2`` layers."""
    paths = {k: p for k, p in D.program_paths(c).items()
             if p[0] != "blocks"}
    for slot, kind in enumerate(PATTERN):
        paths.update({f"{kind}.{k}": ("blocks", slot) + p
                      for k, p in D.SLOT_PATHS.items()})
    return paths


def shapes(c: Dict) -> Dict[str, tuple]:
    per_slot = D.shapes(dict(c, num_hidden_layers=c["num_hidden_layers"]
                             // len(PATTERN)))
    out = {k: s for k, s in per_slot.items() if k not in D.LAYER}
    for kind in PATTERN:
        out.update({f"{kind}.{k}": per_slot[k] for k in D.LAYER})
    return out


def init(c: Dict, key):
    return D.draw(shapes(c), key)


def loss(c: Dict, w: Dict, batch: Dict, rnd: Callable = lambda x: x,
         alter: Optional[Callable] = None):
    seg, pos = batch["segment_ids"], batch["positions"]
    h = w["embed"][batch["tokens"]]

    @jax.checkpoint
    def group(h, p):                      # one layer of each kind
        for kind in PATTERN:
            window = c["sliding_window"] if kind == "local" else 0
            h = D.block(c, p[kind], h, seg, pos, rnd, alter, window)
        return h, None

    slots = {kind: {k: w[f"{kind}.{k}"] for k in D.LAYER}
             for kind in PATTERN}
    h, _ = jax.lax.scan(group, h, slots)
    return D.head_loss(c, w, h, batch, rnd)


def step_flops(c: Dict, batch: Dict) -> float:
    """As the dense decoder's, with the local layers' attention over the
    pairs inside their window."""
    seg, pos = np.asarray(batch["segment_ids"]), batch["positions"]
    tokens = int((seg > 0).sum())
    pairs = flops.live_pairs(seg, pos) \
        + flops.live_pairs(seg, pos, c["sliding_window"])
    fwd = tokens * D.linear_flops_per_token(c) \
        + ca_layers(c) * D.attention_fwd_flops(c, pairs)
    return 3.0 * fwd


ca_call_work = D.ca_call_work


def ca_layers(c: Dict) -> int:
    """The global half of the layers."""
    return c["num_hidden_layers"] // len(PATTERN)
