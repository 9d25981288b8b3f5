"""The generators: the same seed gives the same tokens, another seed other
ids at the same shapes; weights follow the seed the same way."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib  # noqa: E402,F401
import common  # noqa: E402

evalk = common.load_module("kinds", "eval")
traink = common.load_module("kinds", "train")
BIG = 2 ** 31 + 11


def test_eval_prompts_follow_the_seed():
    a = evalk.EvalTraffic(BIG, 49152, 64, 1024)
    b = evalk.EvalTraffic(BIG, 49152, 64, 1024)
    c = evalk.EvalTraffic(BIG + 1, 49152, 64, 1024)
    assert np.array_equal(a.batch(3), b.batch(3))
    assert a.batch(3).shape == c.batch(3).shape == (64, 1024)
    assert not np.array_equal(a.batch(3), c.batch(3))
    assert not np.array_equal(a.batch(3), a.batch(4))
    assert a.batch(0).min() >= 0 and a.batch(0).max() < 49152


def test_train_batches_follow_the_seed_and_rows_differ():
    a = traink.TrainTraffic(BIG, 49152, 4, 1024).batch(2)
    b = traink.TrainTraffic(BIG, 49152, 4, 1024).batch(2)
    c = traink.TrainTraffic(BIG + 1, 49152, 4, 1024).batch(2)
    for k in ("tokens", "labels", "weights"):
        assert np.array_equal(a[k], b[k])
        assert a[k].shape == c[k].shape == (4, 1024)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert len({r.tobytes() for r in a["tokens"]}) == 4


def test_weights_follow_the_seed_layer_by_layer():
    import jax
    import jax.numpy as jnp
    tree = {"embed": {"tok": jax.ShapeDtypeStruct((64, 8), jnp.float32)},
            "segments": [({"mlp": {"w1": jax.ShapeDtypeStruct(
                (3, 8, 16), jnp.float32)}},)]}
    conf = {"reference": "dense"}
    w = common.make_weights(BIG, conf, tree)
    again = common.make_weights(BIG, conf, tree)
    other = common.make_weights(BIG + 2 ** 32, conf, tree)
    w1 = np.asarray(w["segments"][0][0]["mlp"]["w1"])
    assert np.array_equal(w1, np.asarray(again["segments"][0][0]["mlp"]["w1"]))
    assert not np.array_equal(
        w1, np.asarray(other["segments"][0][0]["mlp"]["w1"]))
    # one layer alone, jitted as the reference makes it: the same numbers
    layer1 = jax.jit(lambda k, layer: common.leaf_value(
        common.leaf_key(k, "segments/mlp/w1", layer), "segments/mlp/w1",
        (8, 16), "dense"))(common.base_key(BIG), jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(layer1), w1[1])
    assert abs(float(w1.std()) - 8 ** -0.5) < 0.1
