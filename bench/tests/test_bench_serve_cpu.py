"""A serving cell end to end on the CPU at a tiny size: the program's
served tokens agree with the plain reference, and a token altered where
the engine samples it makes ``correct`` false."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from bench_tiny import cell, run

from bench import serve, weights
from bench.reference import dense


def test_chat_cell_is_correct(monkeypatch):
    out = run(monkeypatch, "chatglm3-6b.chat", seconds=3.0)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 10
    m = out["metrics"]
    assert set(m) == {"ttft_p90_s", "serve_output_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"


def test_a_token_altered_where_it_is_sampled_is_caught(monkeypatch):
    import repro.serve.engine as eng
    real = eng.sample_tokens

    def altered(*a, **kw):
        return (real(*a, **kw) + 1) % 256

    monkeypatch.setattr(eng, "sample_tokens", altered)
    out = run(monkeypatch, "chatglm3-6b.chat", seconds=2.0)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_reference_matches_program_prefill_and_decode():
    """The reference's logits against the program's prefill of a prompt
    and its decode steps through the KV cache, at a tiny size."""
    import jax
    from repro import api
    from repro.serve import Request, ServeConfig
    _, _, cfg, mix = cell("chatglm3-6b.chat")
    from bench import common
    m = common.model_dims(cfg)
    w = weights.make(m, jax.random.PRNGKey(3), jnp.bfloat16)
    program = api.compile(common.family(m).arch_config(cfg, m))
    eng = program.serve(weights.to_program(w, m), ServeConfig(
        max_batch=4, s_max=256, prefill_buckets=(32, 64, 128)))
    prompt = np.arange(5, 45, dtype=np.int32) % m["vocab"]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=12))
    eng.run()
    out = np.asarray(eng.finished[0].output)
    gap = serve.logit_gaps(w, m, [(prompt, out)], pad_to=64)[0]
    assert gap.max() < 0.05
    # the reference's own greedy continuation differs from random tokens
    rnd = (out + 7) % m["vocab"]
    assert serve.logit_gaps(w, m, [(prompt, rnd)], pad_to=64)[0].max() > 0.05
    z = dense.logits(w, jnp.asarray(np.concatenate([prompt, out])[None]), m)
    assert z.shape == (1, 52, m["vocab"]) and bool(jnp.isfinite(z).all())
