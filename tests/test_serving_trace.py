"""The engine's host spans and program names: a tiny chunked engine served
under ``jax.profiler`` and read back with ``bench.spans`` shows every tick
and request phase, the profiler changes no token, and the paged steps
lower to programs named for what they run."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spans as sp  # noqa: E402

PROMPT_LENS = (3, 9, 6, 11)
GENS = (3, 2, 4, 2)


def _tiny_cfg():
    from repro.configs.base import ArchConfig, BlockSpec
    return ArchConfig(
        name="tiny-serve", family="dense", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab=128,
        pattern=(BlockSpec("attn", "dense"),), qkv_bias=True,
        tie_embeddings=True, remat="none")


@pytest.fixture(scope="module")
def tiny_model():
    from repro.models import init_params
    cfg = _tiny_cfg()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, **kw):
    from repro.serving import PagedServingEngine
    return PagedServingEngine(cfg, params, page_size=4, max_concurrency=2,
                              max_seq_len=24, prefill_chunk=4, **kw)


def _serve(cfg, params, **kw):
    """Serve the stream tick by tick: ``(engine, streams, ticks)``."""
    rng = np.random.default_rng(0)
    eng = _engine(cfg, params, **kw)
    for n, g in zip(PROMPT_LENS, GENS):
        eng.submit(list(rng.integers(0, cfg.vocab, n)), g)
    ticks = 0
    while not eng.scheduler.done:
        eng.step()
        ticks += 1
    return eng, dict(eng.scheduler.completed), ticks


def _traced(tmp_path, cfg, params, **kw):
    with jax.profiler.trace(str(tmp_path)):
        _eng, out, ticks = _serve(cfg, params, **kw)
    return out, ticks, sp.load(str(tmp_path))


def _inside(spans, outer, name):
    return [s for s in spans if s.name == name
            and outer.start <= s.start and s.end <= outer.end]


def test_every_tick_and_request_phase_has_its_span(tiny_model, tmp_path):
    cfg, params = tiny_model
    out, ticks, t = _traced(tmp_path, cfg, params)
    spans = t.spans
    steps = [s for s in spans if s.name == "serving.step"]
    assert len(steps) == ticks
    for st in steps:
        assert len(_inside(spans, st, "serving.schedule")) == 1
        if st.stats["n_decode"]:
            assert len(_inside(spans, st, "serving.decode")) == 1
            kinds = [s.stats["kind"]
                     for s in _inside(spans, st, "serving.sync")]
            assert "decode" in kinds
        assert len(_inside(spans, st, "serving.prefill")) == \
            st.stats["n_prefill"]
        assert len(_inside(spans, st, "serving.admit")) == st.stats["n_admit"]
    assert any(st.stats["n_decode"] for st in steps)

    admitted = [s.stats["rid"] for s in spans if s.name == "serving.admit"]
    first = [s.stats["rid"] for s in spans if s.name == "serving.sync"
             and s.stats["kind"] == "first_token"]
    assert sorted(admitted) == sorted(first) == sorted(out)
    # each request's chunks carry its whole prompt, in order
    for rid, n in zip(sorted(out), PROMPT_LENS):
        chunks = [s for s in spans if s.name == "serving.prefill"
                  and s.stats["rid"] == rid]
        assert [c.stats["start"] for c in chunks] == list(range(0, n, 4))
        assert sum(c.stats["tokens"] for c in chunks) == n
    assert sum(st.stats["n_admit"] for st in steps) == len(out)

    r = sp.reduce(t)
    assert sp.host_ms_per_tick(r) > 0
    assert sp.prefill_latency_p90_ms(r) > 0


def test_profiler_changes_no_token(tiny_model, tmp_path):
    cfg, params = tiny_model
    traced, _ticks, _t = _traced(tmp_path, cfg, params)
    _eng, plain, _ = _serve(cfg, params)
    assert traced == plain
    assert [len(plain[r]) for r in sorted(plain)] == list(GENS)


def test_speculative_tick_spans(tiny_model, tmp_path):
    """A verify tick proposes, dispatches, syncs on ``verify`` and
    records, and serves the plain engine's tokens."""
    from repro.spec import SpecConfig
    cfg, params = tiny_model
    out, _ticks, t = _traced(tmp_path, cfg, params, speculative=SpecConfig(k=2))
    _eng, plain, _ = _serve(cfg, params)
    assert out == plain
    names = {s.name for s in t.spans}
    assert {"serving.propose", "serving.decode", "serving.record"} <= names
    assert "verify" in {s.stats.get("kind") for s in t.spans
                        if s.name == "serving.sync"}


def test_paged_steps_lower_to_named_programs(tiny_model):
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    b = len(eng.seq_lens)
    bt, sl = jnp.asarray(eng.block_table), jnp.asarray(eng.seq_lens)
    act = jnp.ones((b,), jnp.bool_)
    i32 = jnp.int32
    decode = eng._decode_jit.lower(params, jnp.zeros((b, 1), i32), eng.caches,
                                   bt, sl, act, None)
    chunk = eng._chunk_jit.lower(params, jnp.zeros((1, 4), i32), eng.caches,
                                 bt[:1], sl[:1], None, jnp.asarray([3], i32))
    verify = eng._verify_fn.lower(params, jnp.zeros((b, 3), i32), eng.caches,
                                  bt, sl, act, jnp.zeros((b,), i32))
    assert "@jit_paged_decode_step" in decode.as_text()
    assert "@jit_paged_prefill_chunk" in chunk.as_text()
    assert "@jit_paged_verify_step" in verify.as_text()
    # one entry point routes both shapes to their own program
    _eng, _out, _ = _serve(cfg, params)
    assert _eng._decode_jit._cache_size() == 1
    assert _eng._chunk_jit._cache_size() == 1
