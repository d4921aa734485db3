"""Operation and byte counts of ``costs/dense.py`` against the program's
parameter tree and against counts worked by hand."""
import numpy as np
import pytest

import harness
import model


def _costs(spec):
    return harness.load_module("costs", spec.family)


def _spec(name):
    conf = model.load_config(name)
    return harness.family(conf["family"]).spec_of(conf)


@pytest.mark.parametrize("name", ["smollm-360m", "stablelm-3b"])
def test_params_equal_the_programs_tree(name):
    from repro.configs import get_config
    from repro.models.lm import LM
    spec = _spec(name)
    tree, _ = LM(get_config(spec.arch)).init(None, abstract=True)
    have = sum(int(np.prod(a.shape)) for a in _leaf_list(tree))
    assert _costs(spec).params(spec) == have


def _leaf_list(tree):
    import jax
    return jax.tree.leaves(tree)


def test_smollm_counts_by_hand():
    spec = _spec("smollm-360m")
    c = _costs(spec)
    # one layer: q 960*960 + k,v 2*960*320 + o 960*960 + gate,up,down
    # 3*960*2560 = 921600 + 614400 + 921600 + 7372800
    assert c.layer_matmul_params(spec) == 9_830_400
    # 32 layers + the tied head 960 * 49152
    assert c.matmul_params(spec) == 314_572_800 + 47_185_920
    # weights read by a decode step: bf16 matmul weights, f32 norms
    # (2 * 32 + 1) * 960
    assert c.weight_bytes(spec) == 2 * 361_758_720 + 4 * 62_400
    # K and V of a position: 2 * 32 layers * 5 heads * 64 * 2 bytes
    assert spec.kv_bytes_per_token == 40_960
    # one step at position 99: weights, 100 cached positions, 1 new one
    assert c.decode_bytes(spec, 1, [(99, 99)]) == \
        723_767_040 + 100 * 40_960 + 40_960
    # per trained token at 2048: 3 * (2 * 361_758_720 matmul
    # + 4 * 32 * 15 * 64 * 2049 / 2 attention)
    assert c.train_flops_per_token(spec, 2048) == \
        3 * (723_517_440 + 125_890_560)


def test_stablelm_kv_is_eight_times_smollm():
    big = _spec("stablelm-3b")
    assert big.kv_bytes_per_token == 327_680
    assert big.rot_dim == 20
