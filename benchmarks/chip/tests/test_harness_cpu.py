"""The harness end to end at a tiny size, through the real engine on the
``ref`` backend, called as functions: the result line's keys, a traced
run's per-layer metrics, a token altered where it is produced coming out
as not correct, and the command's refusal of a host without a TPU."""
import json
import os
import subprocess
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, "tests"))

import harness  # noqa: E402
from tiny_cell import tiny_cell  # noqa: E402

SEED = 2**33 + 7
E2E = {"ttft_p90_s", "itl_p50_ms", "hbm_peak_gb", "setup_s"}


def run(cell, trace=False, seed=SEED):
    return harness.run(cell, seed, 3.0, trace, t_start=harness.clock(),
                       require_tpu=False, backend="ref", cache=False,
                       log=lambda _m: None)


def test_run_prints_the_five_keys_and_is_correct():
    out = run(tiny_cell())
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "check"
    assert out["correct"] is True
    assert out["attempted"] == round(4.0 * 3.0) and out["failed"] == 0
    assert set(out["metrics"]) == E2E
    assert all(m["value"] >= 0 for m in out["metrics"].values())
    assert out["metrics"]["itl_p50_ms"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    chk = out["check"]
    assert chk["widest_gap"]["value"] <= chk["widest_gap"]["limit"] == 0.2
    assert chk["short_requests"] == {"value": 0, "limit": 0}
    json.loads(json.dumps(out))


def test_traced_run_reports_per_layer_metrics():
    out = run(tiny_cell(), trace=True)
    assert "breakdown" in out and set(out["breakdown"]) == {
        "device_ops", "idle_gaps"}
    # the CPU has no TPU planes: device readers find nothing and stay
    # silent; the host's and the engine's counters are read
    assert set(out["metrics"]) == {"queue_wait_p90_s",
                                   "preempted_per_100_req",
                                   "kv_pool_peak_pct", "itl_tail_p95_ms"}
    assert out["metrics"]["itl_tail_p95_ms"]["value"] > 0
    assert 0 < out["metrics"]["kv_pool_peak_pct"]["value"] <= 100
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert out["correct"] is True


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serving import scheduler

    orig = scheduler.ContinuousBatchingEngine._record

    def altered(self, req, token):
        tok = token.tolist() if hasattr(token, "tolist") else token
        if len(req.out_tokens) == 2:
            tok = (int(tok) + 1) % 512
        return orig(self, req, tok)

    monkeypatch.setattr(scheduler.ContinuousBatchingEngine, "_record",
                        altered)
    out = run(tiny_cell())
    assert out["correct"] is False
    assert out["check"]["widest_gap"]["value"] > 0.2


def test_decode_step_returning_its_cache_unchanged_is_not_correct(
        monkeypatch):
    build = harness.build

    def stale(*args, **kw):
        cfg, engine = build(*args, **kw)
        step = engine._decode_paged

        def decode(params, pools, *rest):
            logits, _ = step(params, pools, *rest)
            return logits, pools          # the new K/V never reach the pool
        engine._decode_paged = decode
        return cfg, engine

    monkeypatch.setattr(harness, "build", stale)
    out = run(tiny_cell())
    assert out["correct"] is False
    assert out["check"]["widest_gap"]["value"] > 0.2


def test_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "stablelm-1.6b.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_warm_lengths_cover_every_bucket_of_the_chat_mix():
    cell = harness.load_cell("stablelm-1.6b.chat")
    lengths = harness.warm_lengths(cell["mix"])
    # full-block parts of prompts 32..2048 fall in buckets 16..2048, and a
    # preemption's resume up to the 2816-token cache
    assert lengths == [17, 33, 65, 129, 257, 513, 1025, 2049, 2801]
