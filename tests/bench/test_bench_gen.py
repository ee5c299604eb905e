"""The traffic generators and the seeded weights: deterministic per seed,
the same work for every seed, and clipped as the mix says."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_cases  # noqa: F401  (puts the repository on the path)
from bench import gen, spec, weights

MIX = spec.read_json("bench/traffic/chat-sparse.json")


def test_chat_is_deterministic_per_seed():
    a = gen.chat_arrivals(MIX, 2**31 + 5, 30, 1000)
    b = gen.chat_arrivals(MIX, 2**31 + 5, 30, 1000)
    assert [(x.due, x.max_new) for x in a] == [(x.due, x.max_new) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_chat_same_work_other_order():
    a = gen.chat_arrivals(MIX, 1, 30, 1000)
    b = gen.chat_arrivals(MIX, 2, 30, 1000)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


@pytest.mark.parametrize("mix", ["chat-sparse", "chat-sparse-sat"])
def test_chat_rate_and_clipping(mix):
    m = spec.read_json(f"bench/traffic/{mix}.json")
    arr = gen.chat_arrivals(m, 7, 30, 152064)
    assert "rate_per_s" not in m
    assert gen.rate_per_s(m) == m["knee_per_s"] * m["load_of_knee"]
    assert len(arr) == round(gen.rate_per_s(m) * 30)
    due = [x.due for x in arr]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 30
    p = np.array([len(x.prompt) for x in arr])
    o = np.array([x.max_new for x in arr])
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    assert all(x.prompt.max() < 152064 for x in arr)
    # every request fits the cache the configuration serves
    srv = spec.read_json("bench/configs/qwen2-7b-16l.json")["serving"]
    assert (p + o).max() <= srv["max_len"]


def test_listops_rows():
    a = gen.listops_batches(3, count=2, batch=4, seq_len=256)
    b = gen.listops_batches(3, count=2, batch=4, seq_len=256)
    assert all(np.array_equal(x["tokens"], y["tokens"]) for x, y in zip(a, b))
    t = a[0]["tokens"]
    assert t.shape == (4, 256) and t.max() < gen.LISTOPS_VOCAB
    assert (t[:, 0] == gen.CLS).all()
    assert np.array_equal(a[0]["tokens"][:, 1:], a[0]["labels"][:, :-1])
    lengths = (a[0]["tokens"] != gen.PAD).sum(1)
    assert (np.diff(lengths) >= 0).all()
    rows = np.concatenate([x["tokens"] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_weights_are_drawn_again_alike(monkeypatch):
    monkeypatch.setattr(weights, "CHUNK_ELEMS", 64)
    shapes = {"layers": {"attn": {"wq": jax.ShapeDtypeStruct((3, 8, 8),
                                                            jnp.bfloat16)}},
              "tok_embed": {"w": jax.ShapeDtypeStruct((32, 8), jnp.float32)},
              "final_norm": {"scale": jax.ShapeDtypeStruct((8,), jnp.float32)}}
    for seed in (2**33 + 9, 2**31 + 11):
        key = weights.seed_key(seed)
        w = weights.make(shapes, seed)
        assert w["layers"]["attn"]["wq"].dtype == jnp.bfloat16
        draw = jax.jit(weights.layer_leaf, static_argnums=(1, 2, 3))
        for layer in range(3):
            one = draw(key, "layers/attn/wq", (8, 8), jnp.bfloat16, layer)
            assert np.array_equal(
                np.asarray(one, np.float32),
                np.asarray(w["layers"]["attn"]["wq"][layer], np.float32))
        emb = weights.unstacked_leaf(key, "tok_embed/w", (32, 8), jnp.float32)
        assert np.array_equal(np.asarray(emb), np.asarray(w["tok_embed"]["w"]))
    other = weights.make(shapes, seed + 1)
    assert not np.array_equal(np.asarray(other["tok_embed"]["w"]),
                              np.asarray(w["tok_embed"]["w"]))
    assert abs(float(np.mean(w["final_norm"]["scale"])) - 1.0) < 0.2
