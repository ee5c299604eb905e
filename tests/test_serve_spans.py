"""The serving engine's in-memory spans (repro.launch.spans): which spans a
paged engine records, how they nest, and that the record stays bounded."""
import jax
import numpy as np

from repro.configs import get_config
from repro.launch import spans
from repro.launch.serve import Request, ServeEngine
from repro.models.registry import build

NAMES = {"serve.admit", "serve.decode", "serve.decode.launch",
         "serve.decode.fetch"}


def _engine(**kw):
    cfg = get_config("qwen2-7b").reduced().replace(
        remat=False, dtype="float32", cache_dtype="float32")
    params = build(cfg).init(jax.random.key(0))
    return cfg, ServeEngine(cfg, params, paged=True, **kw)


def _drive(eng, reqs):
    """Step the engine to completion; returns the number of steps."""
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.waiting or any(r is not None for r in eng.active):
        eng.step()
        steps += 1
    return steps


def _inside(child, parents):
    return sum(p[2] <= child[2] and child[3] <= p[3] for p in parents)


def test_engine_records_its_spans_nested():
    cfg, eng = _engine(slots=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_new=m)
            for i, (n, m) in enumerate([(9, 4), (17, 3), (5, 5)])]
    spans.RECORD.clear()
    steps = _drive(eng, reqs)
    recs = list(spans.RECORD)
    by = {n: [r for r in recs if r[0] == n] for n in NAMES}
    assert {r[0] for r in recs} == NAMES
    # every step decodes (max_new > 1): one serve.decode a tick, each
    # holding exactly one launch and one fetch, launch first
    dec = by["serve.decode"]
    assert len(dec) == steps
    assert all(1 <= r[1]["active"] <= 2 for r in dec)
    for kid in ("serve.decode.launch", "serve.decode.fetch"):
        assert len(by[kid]) == steps
        assert all(_inside(k, dec) == 1 and k[1] == {} for k in by[kid])
    for d, la, fe in zip(dec, by["serve.decode.launch"],
                         by["serve.decode.fetch"]):
        assert d[2] <= la[2] <= la[3] <= fe[2] <= fe[3] <= d[3]
    # one serve.admit per admitted request, outside every decode, with the
    # request's own ids
    adm = by["serve.admit"]
    assert sorted(r[1]["rid"] for r in adm) == [0, 1, 2]
    for r in adm:
        req = reqs[r[1]["rid"]]
        assert r[1] == {"rid": req.rid, "slot": req.slot,
                        "prompt_len": len(req.prompt)}
        assert _inside(r, dec) == 0
        assert all(type(v) is int for v in r[1].values())
    # a child's record precedes its parent's (appended at exit)
    assert recs.index(by["serve.decode.fetch"][0]) < recs.index(dec[0])


def test_an_attempt_the_pool_cannot_cover_is_recorded():
    """With pages for one request at a time, the second request's
    admission is attempted (and recorded) until the first one's pages
    return; its last attempt admits it."""
    cfg, eng = _engine(slots=2, max_len=64, num_pages=3)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, 40)
                    .astype(np.int32), max_new=4) for i in range(2)]
    spans.RECORD.clear()
    _drive(eng, reqs)
    adm = [r for r in spans.RECORD if r[0] == "serve.admit"]
    rids = [r[1]["rid"] for r in adm]
    assert rids[0] == 0 and rids.count(0) == 1 and rids.count(1) > 1
    assert all(r.done for r in reqs)


def test_the_record_is_bounded():
    assert spans.RECORD.maxlen == 1 << 16
    spans.RECORD.clear()
    for i in range(spans.RECORD.maxlen + 5):
        with spans.span("serve.decode", active=i):
            pass
    assert len(spans.RECORD) == spans.RECORD.maxlen
    assert spans.RECORD[0][1] == {"active": 5}
    assert spans.RECORD[-1][1] == {"active": spans.RECORD.maxlen + 4}
    spans.RECORD.clear()
