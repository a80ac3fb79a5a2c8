"""The serving engine's own instrumentation: the ``serving.*`` host spans it
writes into the profiler's trace, the ``host_syncs`` counter, the
``admitted_at`` stamp, and the names its jitted programs carry."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro import configs as C
from repro.api.backends import use_backend
from repro.models import init_params
from repro.serving.scheduler import ContinuousBatchingEngine

SPANS = ("serving.step", "serving.admit", "serving.prefill",
         "serving.ensure_blocks", "serving.decode", "serving.harvest")


@pytest.fixture(scope="module")
def setup():
    cfg = C.smoke_config("mistral-nemo-12b").with_overrides(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(params, cfg, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    return ContinuousBatchingEngine(params, cfg, paged=True, backend="ref",
                                    **kw)


def _prompts(cfg, lengths, seed=1):
    key = jax.random.PRNGKey(seed)
    return [jax.random.randint(jax.random.fold_in(key, i), (1, s), 0,
                               cfg.vocab_size)
            for i, s in enumerate(lengths)]


def _host_events(trace_dir):
    """Every ``serving.*`` host event of the trace: (name, start, end,
    stats)."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serving."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def test_spans_in_profile(setup, tmp_path):
    cfg, params = setup
    engine = _engine(params, cfg)
    engine.warmup(prompt_len=20)
    reqs = [engine.submit(p, max_new_tokens=3)
            for p in _prompts(cfg, (20, 12))]
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run()
    finally:
        jax.profiler.stop_trace()
    assert all(r.done for r in reqs)
    events = _host_events(str(tmp_path))
    assert {e[0] for e in events} == set(SPANS)
    steps = [e for e in events if e[0] == "serving.step"]
    for name in ("serving.decode", "serving.harvest"):
        for _, a, b, _ in (e for e in events if e[0] == name):
            assert any(sa <= a and b <= sb for _, sa, sb, _ in steps), name
    for _, _, _, args in (e for e in events if e[0] == "serving.decode"):
        assert 1 <= int(args["live"]) <= 2
        assert int(args["context"]) > int(args["live"])
    prefills = [e[3] for e in events if e[0] == "serving.prefill"]
    # both prompts prefill their full-block prefix; the tail rides decode
    assert sorted((int(s["rid"]), int(s["tokens"])) for s in prefills) == [
        (reqs[0].rid, 16), (reqs[1].rid, 8)]


def test_host_syncs_count_tokens_and_hashes(setup):
    cfg, params = setup
    engine = _engine(params, cfg)
    engine.warmup()
    assert engine.metrics()["host_syncs"] == 0
    reqs = [engine.submit(p, max_new_tokens=n)
            for p, n in zip(_prompts(cfg, (5, 19, 30), seed=3), (4, 7, 2))]
    engine.run()
    assert all(r.done for r in reqs)
    emitted = sum(len(r.out_tokens) for r in reqs)
    assert emitted == 13
    # one read per emitted token, one per prompt hashed at admission
    assert engine.metrics()["host_syncs"] == emitted + len(reqs)


def test_admitted_at_survives_preemption(setup):
    cfg, params = setup
    engine = _engine(params, cfg, n_slots=3, n_blocks=8)
    reqs = [engine.submit(p, max_new_tokens=10)
            for p in _prompts(cfg, (10, 12, 13), seed=12)]
    first = {}
    while engine.has_work:
        engine.step()
        for r in reqs:
            if r.admitted_at and r.rid not in first:
                first[r.rid] = r.admitted_at
    assert engine.preempted_total > 0, "pool was sized to force preemption"
    resumed = [r for r in reqs if r.preemptions]
    assert resumed
    for r in reqs:
        assert r.done
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
        assert r.admitted_at == first[r.rid]


def test_decode_program_is_named(setup):
    cfg, params = setup
    engine = _engine(params, cfg)
    kv = engine.kv
    with use_backend(engine.backend):
        text = engine._decode_paged.__wrapped__.lower(
            engine.params, kv.pools, engine.last_tokens, engine.positions,
            kv.tables).as_text()
    assert "module @jit_decode_step_paged " in text
    assert engine._prefill_paged.__name__ == "prefill_paged"
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    with use_backend(engine.backend):
        text = engine._prefill_paged.__wrapped__.lower(
            engine.params, kv.pools, batch, jnp.int32(8),
            kv.tables[:1]).as_text()
    assert "module @jit_prefill_paged " in text


def test_decode_span_counts_walked_blocks(setup, tmp_path):
    """``serving.decode``'s ``blocks`` is what the paged kernel walks: per
    slot ``min(pos // bs, M - 1) + 1`` over the positions the decode step
    is given, where an idle slot sits at position 0 and costs one block."""
    cfg, params = setup
    engine = _engine(params, cfg, n_slots=4)
    engine.warmup(prompt_len=20)
    bs, m = engine.kv.block_size, engine.kv.max_blocks
    seen = []
    decode = engine._decode_paged

    def recording(p, c, t, pos, tabs):
        seen.append(jax.device_get(pos))
        return decode(p, c, t, pos, tabs)

    engine._decode_paged = recording
    # the longest request reaches the table's last block (M - 1);
    # slot 3 stays idle throughout and slot 1 idles once its request ends
    reqs = [engine.submit(p, max_new_tokens=n)
            for p, n in zip(_prompts(cfg, (50, 12, 20), seed=5), (12, 3, 6))]
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run()
    finally:
        jax.profiler.stop_trace()
    assert all(r.done for r in reqs)
    decodes = sorted((e for e in _host_events(str(tmp_path))
                      if e[0] == "serving.decode"), key=lambda e: e[1])
    assert len(decodes) == len(seen) > 0
    assert max(int(p.max()) for p in seen) > (m - 1) * bs
    assert any(int(p[1]) == 0 for p in seen[1:])
    for (_, _, _, args), pos in zip(decodes, seen):
        assert pos[3] == 0
        want = sum(min(int(x) // bs, m - 1) + 1 for x in pos)
        assert int(args["blocks"]) == want
