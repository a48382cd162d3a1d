"""The benchmark's reference against the program's own float32 reference
(``repro.models.reference``) at smoke size, and its weights against the
program's initialisation."""
import jax
import numpy as np
import pytest

from bench import reference
from bench.run import build_model

SMALL = {
    "tied_bias": {"bench_model": "dense",
                  "hidden_size": 64, "intermediate_size": 128,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "num_hidden_layers": 2, "vocab_size": 256,
                  "rope_theta": 1e6, "rms_norm_eps": 1e-6,
                  "tie_word_embeddings": True,
                  "architecture": {"qkv_bias": True}},
    "untied_wide_heads": {"bench_model": "dense",
                          "hidden_size": 64, "intermediate_size": 96,
                          "num_attention_heads": 8, "num_key_value_heads": 2,
                          "head_dim": 16, "num_hidden_layers": 3,
                          "vocab_size": 512, "rope_theta": 1e6,
                          "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
                          "architecture": {"qkv_bias": False}},
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_matches_program_reference(name):
    from repro.models.reference import greedy_agreement, reference_logits
    cfg = SMALL[name]
    seed = 2**31 + 3
    model = build_model(cfg)
    params = jax.jit(model.init_params)(reference.params_key(seed))
    rng = np.random.default_rng(0)
    seqs = [(rng.integers(0, cfg["vocab_size"], n).astype(np.int32),
             rng.integers(0, cfg["vocab_size"], k).astype(np.int32))
            for n, k in ((37, 9), (5, 20))]
    got = reference.check_sequences(cfg, seed, seqs)
    for (prompt, served), r in zip(seqs, got):
        logits = reference_logits(model, params,
                                  np.concatenate([prompt, served]))
        gap, lp, _ = greedy_agreement(logits, len(prompt), served)
        assert r["max_gap"] == pytest.approx(gap, abs=2e-4)
        assert r["mean_lp"] == pytest.approx(lp, abs=2e-4)


def test_weights_are_the_programs():
    cfg = SMALL["untied_wide_heads"]
    dm = reference.Dims.from_config(cfg)
    key = reference.params_key(12345)
    params = jax.jit(build_model(cfg).init_params)(key)
    mine = reference._layer_weights(
        jax.random.split(jax.random.split(key, 3)[1], dm.n_layers)[1], dm,
        False)
    theirs = jax.tree.map(lambda a: np.asarray(a[1], np.float32),
                          params["blocks"])
    np.testing.assert_array_equal(mine["wq"], theirs["wq"])
    np.testing.assert_array_equal(mine["wd"], theirs["mlp"]["w_down"])
    np.testing.assert_array_equal(reference._head_weight(key, dm, False),
                                  np.asarray(params["lm_head"], np.float32))
