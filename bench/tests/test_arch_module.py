"""The architecture lives in each configuration's reference module, and
the two cells read exactly what they read when the harness itself held
the dense decoder.  ``data/arch_golden.json`` was recorded from that
harness: the program's ``ModelConfig`` and parameter tree of each
configuration file, the step FLOPs and the work of one CA-server call
(forward and backward) of each of the 6 layouts of the cell's mix, and
the number of layers ``kernel_roofline`` divided by."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from harness import compare, program
from harness.traffic import Traffic

BENCH = Path(__file__).resolve().parents[1]
GOLDEN = json.loads((BENCH / "tests" / "data" /
                     "arch_golden.json").read_text())
CELLS = {"smollm-360m": "prolong-4k", "mistral-7b": "prolong-8k"}


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _plain(x):
    return json.loads(json.dumps(x))


@pytest.mark.parametrize("name", CELLS)
def test_model_config_is_the_recorded_one(name):
    from repro.configs import get_config
    c = config(name)
    mc = program.model_config(c, compare.reference_module(c))
    want = GOLDEN["model_config"][name]
    assert _plain(dataclasses.asdict(mc)) == want
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in want.items()}
    assert mc == dataclasses.replace(get_config(c["registry_arch"]),
                                     **fields)


@pytest.mark.parametrize("name", CELLS)
def test_parameter_tree_is_the_recorded_one(name):
    c = config(name)
    ref = compare.reference_module(c)
    mc = program.model_config(c, ref)
    paths = ref.program_paths(c)
    tree = jax.eval_shape(lambda: program.to_program(
        {k: jnp.zeros(s, jnp.float32) for k, s in ref.shapes(c).items()},
        mc.pdtype, paths))
    want = GOLDEN["tree"][name]
    assert str(jax.tree.structure(tree)) == want["structure"]
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    assert [[jax.tree_util.keystr(p), list(x.shape), str(x.dtype)]
            for p, x in leaves] == want["leaves"]
    program.check_layout(mc, ref.shapes(c), paths)


@pytest.mark.parametrize("layout", range(6))
@pytest.mark.parametrize("name", CELLS)
def test_step_and_call_work_are_the_recorded_ones(name, layout):
    c = config(name)
    ref = compare.reference_module(c)
    mix = json.loads((BENCH / "traffic" /
                      f"{CELLS[name]}.json").read_text())
    lay = Traffic(mix, ranks=1, vocab=c["vocab_size"], seed=0).layouts[layout]
    seg, pos = lay["segment_ids"], lay["positions"]
    want = GOLDEN["work"][f"{name}.{CELLS[name]}"][layout]
    assert ref.step_flops(c, {"segment_ids": seg, "positions": pos}) == \
        want["step_flops"]
    assert ref.ca_call_work(c, seg, pos, backward=False) == want["ca_fwd"]
    assert ref.ca_call_work(c, seg, pos, backward=True) == want["ca_bwd"]


@pytest.mark.parametrize("name", CELLS)
def test_roofline_layer_count_is_the_recorded_one(name):
    c = config(name)
    assert compare.reference_module(c).ca_layers(c) == \
        GOLDEN["ca_layers"][name]
