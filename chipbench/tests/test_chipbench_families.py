"""A model family as files: the dense decoder's numbers as they were
before its statement moved into ``chipbench/families/dense.py``, and a
second family that a temporary root brings as files alone."""
import hashlib

import jax
import numpy as np
import pytest

from chipbench import run
from chipbench.harness import catalog, counts, model, peaks
from chipbench.tests.conftest import TINY_CELL, config, tiny_root

# Taken from counts.py and model.py before the move: (flops, bytes) of
# decode_step and prefill_period at 0, 1500 and 2047, request_flops(spec,
# 1500, 13), and the sha256 of make_weights at SIZES for seed 2**33 + 5.
PINNED = {
    "olmo-1b": {
        "decode_step": {0: (2353659904.0, 2353764608.0),
                        1500: (2550267904.0, 2550372608.0),
                        2047: (2621964288.0, 2622068992.0)},
        "prefill_period": {0: (0.0, 134217728.0),
                           1500: (210548736000.0, 158793728.0),
                           2047: (291915169792.0, 167755776.0)},
        "request_flops": 3399597686784.0,
        "weights": "944f19b7aa16fdf6540451b094615d91"
                   "23bf4e0a374a6c6b88852eabac8742b9",
    },
    "deepseek-coder-33b-pp8": {
        "decode_step": {0: (8947728384.0, 8947854336.0),
                        1500: (9291792384.0, 8997006336.0),
                        2047: (9417261056.0, 9014930432.0)},
        "prefill_period": {0: (0.0, 1060663296.0),
                           1500: (1623229440000.0, 1109815296.0),
                           2047: (2231219257344.0, 1127739392.0)},
        "request_flops": 13097814589440.0,
        "weights": "52de3fa864d038b5517ce85b8b7a2e2c"
                   "5f25b9c154db25bcbd68d04e2c70ad9c",
    },
}
SIZES = dict(num_hidden_layers=2, hidden_size=256, intermediate_size=512,
             num_attention_heads=8, num_key_value_heads=2, vocab_size=512)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_counts_pinned(name):
    spec, want = config(name), PINNED[name]
    for pos, fb in want["decode_step"].items():
        assert counts.decode_step(spec, pos) == fb
    for seq, fb in want["prefill_period"].items():
        assert counts.prefill_period(spec, seq) == fb
    assert counts.request_flops(spec, 1500, 13) == want["request_flops"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_weights_pinned(name):
    spec = dict(config(name), **SIZES)
    assert _digest(model.make_weights(spec, 2 ** 33 + 5)) == \
        PINNED[name]["weights"]


def test_a_configuration_without_a_family_is_refused():
    spec = config("olmo-1b")
    del spec["reference"]
    with pytest.raises(KeyError, match="reference"):
        counts.weight_bytes(spec)


# A family the program already runs, which no file of this checkout
# states: the dense decoder with two ("attn", "mlp") layers in a period.
PAIRED_FAMILY = '''
import dataclasses

from chipbench.families import dense

TINY = dict(dense.TINY, num_hidden_layers=4)
TINY_LIMIT = dense.TINY_LIMIT
weight_bytes = dense.weight_bytes
kv_bytes_per_token = dense.kv_bytes_per_token
decode_step = dense.decode_step
request_flops = dense.request_flops


def arch_config(name, spec):
    return dataclasses.replace(dense.arch_config(name, spec),
                               block_pattern=(("attn", "mlp"),) * 2)


def weight_shapes(spec):
    """slot0 as the dense decoder's, and slot1 alike but for its norm
    scales, which a function of the family's own draws in [0.5, 1.5)."""
    half = dict(spec, num_hidden_layers=spec["num_hidden_layers"] // 2)
    out = {}
    for path, (shape, init) in dense.weight_shapes(half).items():
        out[path] = (shape, init)
        if path.startswith("slots/slot0/"):
            out[path.replace("slot0", "slot1")] = (
                shape, (lambda u: 0.5 + u) if init == "ones" else init)
    return out


def empty_nodes(spec):
    return dense.empty_nodes(spec) + ["slots/slot1/norm1", "slots/slot1/norm2"]


def prefill_period(spec, seq):
    f, b = dense.prefill_period(spec, seq)
    return 2 * f, 2 * b


def prefill_beyond_need(spec, seq):
    return 2 * dense.prefill_beyond_need(spec, seq)
'''
# Its reference: the dense one over the layers in the program's order,
# slot0 then slot1 in each period.
PAIRED_REFERENCE = '''
import jax
import jax.numpy as jnp

from chipbench.reference import dense


def logits(spec, params, tokens, rows, quantize=None, pad_to=512):
    s0, s1 = params["slots"]["slot0"], params["slots"]["slot1"]
    layers = jax.tree.map(
        lambda a, b: jnp.stack([a, b], 1).reshape((-1,) + a.shape[1:]), s0, s1)
    return dense.logits(spec, dict(params, slots={"slot0": layers}), tokens,
                        rows, quantize, pad_to)
'''


def test_family_added_as_files_alone(tmp_path):
    base = tmp_path / "chipbench"
    for kind, text in (("families", PAIRED_FAMILY),
                       ("reference", PAIRED_REFERENCE)):
        (base / kind).mkdir(parents=True)
        (base / kind / "paired.py").write_text(text)
    root = tiny_root(tmp_path, dict(config("deepseek-coder-33b-pp8"),
                                    reference="paired"))
    cell = catalog.find(root, TINY_CELL)
    spec = cell.spec
    assert catalog.reference(spec).__file__ == str(base / "reference/paired.py")

    m, cfg = model.build_model(cell.config_name, spec)
    assert cfg.block_pattern == (("attn", "mlp"),) * 2 and cfg.n_periods == 2
    model.check_layout(m, spec)
    params = model.make_weights(spec, 5)
    assert sorted(params["slots"]) == ["slot0", "slot1"]
    scale = np.asarray(params["slots"]["slot1"]["norm1"]["scale"], np.float32)
    assert 0.5 <= scale.min() < 1.0 < scale.max() < 1.5
    assert counts.weight_bytes(spec) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    one_layer = counts.prefill_period(dict(spec, reference="dense"), 64)
    assert counts.prefill_period(spec, 64) == tuple(2 * x for x in one_layer)

    res = run.run_cell(root, TINY_CELL, 2 ** 33 + 11, 1.5, False,
                       require_tpu=False, peaks=peaks.PEAKS["TPU v5 lite"],
                       log=lambda m: None)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
