"""The harness's verdict, driven on the CPU at a small size: a sound run is
correct, the float8 control put in the program's place reads above the
limits the program stays under and comes out ``correct: false``, and so
does a run whose timed path is broken underneath.

The timed path here is the real one (router, replica, engine, mixed loop);
only the chip check is skipped and the model is small.  Faults, each
planted where the work is produced:

* a token altered as the engine takes it off the device;
* a step that returns its KV state unchanged (the span's keys and values
  are never written).
"""
import dataclasses
import json


from bench import run

SMALL = {"bench_model": "dense",
         "hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 1024, "rope_theta": 1e6,
         "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
         "architecture": {"qkv_bias": True},
         "serve": {"max_batch": 4, "max_len": 256}}
MIX = {"loop": "open",
       "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                         "min": 8, "max": 96},
       "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.8,
                         "min": 4, "max": 48},
       "arrivals": {"process": "balanced_exponential"}}
# limits for this size, set between the readings: sound CPU runs read up to
# 0.0054 (gap) and 0.0021 (score) over a few seeds, the float8 control at
# least 0.062 and 0.019
CELL = {"rate_rps": 3.0, "ramp_s": 0.5, "drain_cap_s": 30.0,
        "ttft_limit_s": 5.0, "tpot_limit_ms": 500.0, "check_tokens": 60,
        "check_requests": 4,
        "correct": {"max_logit_gap": 0.02, "max_score_gap": 0.006}}
E2E = [{"name": n, "unit": u} for n, u in
       (("ttft_p90_s", "s"), ("tpot_p90_ms", "ms"),
        ("output_tokens_per_s", "tokens/s"), ("slo_attainment", "fraction"),
        ("setup_s", "s"))]
SEED = 2**31 + 77


def _run(capsys, control=False):
    spec = {"name": "small", "chips": 1, "config": SMALL, "mix": MIX,
            "cell": CELL, "end_to_end": E2E, "per_layer": []}
    rc = run.run_cell(spec, SEED, 3.0, False, require_tpu=False,
                      control=control, cache=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_control_reads_above_the_limits(capsys):
    res, lines = _run(capsys, control=True)
    assert res["correct"] is False
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    checks, limits = res["checks"], CELL["correct"]
    assert checks["max_logit_gap"]["value"] > limits["max_logit_gap"]
    assert checks["max_score_gap"]["value"] > limits["max_score_gap"]
    assert checks["pages_leaked"]["value"] == 0
    # the program's own readings, on the same requests, stay under them
    prog = [x for x in lines if x.startswith("[bench] program:")][0]
    readings = dict(p.split() for p in prog.split(":", 1)[1].split(","))
    assert float(readings["max_logit_gap"]) <= limits["max_logit_gap"]
    assert float(readings["max_score_gap"]) <= limits["max_score_gap"]


def test_sound_run_is_correct(capsys):
    res, lines = _run(capsys)
    assert res["correct"] is True
    assert all(v["value"] <= v["limit"] for v in res["checks"].values())
    assert any("KV pages held at most" in x for x in lines)
    assert any("functions traced 0)" in x for x in lines)


def test_altered_token_is_not_correct(capsys, monkeypatch):
    from repro.serving import ServingEngine
    orig = ServingEngine._apply_decode_outputs

    def altered(self, rows, out_toks, *a, **k):
        out_toks = out_toks.at[:, 0].set((out_toks[:, 0] + 1) % 1024)
        return orig(self, rows, out_toks, *a, **k)

    monkeypatch.setattr(ServingEngine, "_apply_decode_outputs", altered)
    res, _ = _run(capsys)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > CELL["correct"]["max_logit_gap"]


def test_state_left_unchanged_is_not_correct(capsys, monkeypatch):
    build = run.build_model

    def stale_kv(cfg):
        model = build(cfg)
        verify = model.verify_step

        def step(params, cache, *a, **k):
            tok, lp, _ = verify(params, cache, *a, **k)
            return tok, lp, cache
        return dataclasses.replace(model, verify_step=step)

    monkeypatch.setattr(run, "build_model", stale_kv)
    res, _ = _run(capsys)
    assert res["correct"] is False
