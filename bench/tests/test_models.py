"""Architectures as files of their own: a configuration names its module by
``bench_model`` and the harness finds it by path, builds the served model,
the reference check, the costs and the kernel names from it, and refuses a
name it cannot find.

A stub architecture written to a temporary directory at test time is
served through ``run.run_cell`` on the CPU at ``test_correct.py``'s small
size, checked with its own reference and costed with its own costs, with no
file of the benchmark edited.  The dense module's costs are
``bench/costs.py``'s at the hand-worked qwen2.5-3b values, and the three
readers of costs give what they gave when each built ``Shapes`` itself.
"""
import base64
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import costs as dense_costs
from bench import run
from bench.peaks import peaks
from bench.tests.test_correct import CELL, E2E, MIX, SMALL
from bench.trace import kernel_ops

CFG = json.loads((Path(__file__).parents[1] / "configs"
                  / "qwen2.5-3b.json").read_text())
SEED = 2**32 + 901

STUB = '''
"""A stub architecture: the small dense model under a module of its own,
with a reference, costs and a kernel name of its own."""
import jax.numpy as jnp

from bench import reference
from repro.models.common import ModelConfig

KERNELS = {"stub_attention": "_stub_attention_kernel"}
CALLS = []


def model_config(cfg):
    CALLS.append("model_config")
    return ModelConfig(
        name="stub", family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        qkv_bias=cfg["architecture"]["qkv_bias"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=jnp.bfloat16,
        remat="none")


def params_key(seed):
    CALLS.append("params_key")
    return reference.params_key(seed)


def check_sequences(cfg, seed, seqs, *, control=False):
    CALLS.append("check_sequences")
    return reference.check_sequences(cfg, seed, seqs, control=control)


class Costs:
    n_layers = 7

    def positions_flops(self, start, end):
        return 11.0 * float(sum(e - s for s, e in zip(start, end)))

    def attention_call(self, starts, q_len):
        return 13.0 * q_len * len(starts), 17.0

    def lmhead_call(self, rows):
        return 19.0 * rows, 23.0


def costs(cfg):
    CALLS.append("costs")
    return Costs()
'''


# -- lookup -------------------------------------------------------------------------

def test_configs_name_the_dense_module():
    for path in sorted((Path(__file__).parents[1] / "configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        mod = run.bench_model(cfg)
        assert Path(mod.__file__) == run.MODELS / f"{cfg['bench_model']}.py"
    dense = run.bench_model(CFG)
    assert dense.model_config(CFG).family == "dense"
    assert dense.KERNELS == {}
    assert run.bench_model(CFG) is dense          # loaded once


@pytest.mark.parametrize("cfg", [{}, {"bench_model": "no_such_model"},
                                 {"bench_model": "../reference"}],
                         ids=["missing", "unknown", "outside"])
def test_missing_or_unknown_module_is_refused(cfg):
    with pytest.raises(SystemExit, match=r"known: \[.*'dense'.*\]"):
        run.bench_model(cfg)


def test_lookup_directory_is_a_parameter(tmp_path):
    (tmp_path / "stub.py").write_text(STUB)
    (tmp_path / "other.py").write_text("KERNELS = {}\n")
    assert run.bench_model({"bench_model": "stub"}, tmp_path).KERNELS == {
        "stub_attention": "_stub_attention_kernel"}
    with pytest.raises(SystemExit, match=r"known: \['other', 'stub'\]"):
        run.bench_model({"bench_model": "dense"}, tmp_path)


# -- a new architecture, with no file of the benchmark edited ---------------------------

def test_stub_architecture_is_served_checked_and_costed(tmp_path, monkeypatch,
                                                        capsys):
    (tmp_path / "stub.py").write_text(STUB)
    monkeypatch.setattr(run, "MODELS", tmp_path)
    views = []
    read = run.reader

    def spy(name):
        f = read(name)

        def seen(view):
            views.append(view)
            return f(view)
        return seen

    monkeypatch.setattr(run, "reader", spy)
    cfg = {**SMALL, "bench_model": "stub"}
    spec = {"name": "stub", "chips": 1, "config": cfg, "mix": MIX,
            "cell": CELL, "end_to_end": E2E, "per_layer": []}
    rc = run.run_cell(spec, SEED, 3.0, False, require_tpu=False, cache=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["attempted"] > 0
    stub = run.bench_model(cfg)
    assert stub.CALLS == ["model_config", "params_key", "check_sequences",
                          "costs"]
    assert views and all(isinstance(v.costs, stub.Costs) for v in views)
    # its kernel is named beside the shared ones, and found in the HLO
    names = run.kernel_names(stub)
    assert names == {**run.KERNELS, "stub_attention": "_stub_attention_kernel"}
    body = base64.b64encode(b"func @_stub_attention_kernel").decode()
    hlo = (f'%custom-call.9 = f32[8] custom-call(), '
           f'custom_call_target="tpu_custom_call", '
           f'backend_config={{"custom_call_config": {{"body":"{body}"}}}}')
    assert kernel_ops(hlo, names) == {"custom-call.9": "stub_attention"}


def test_readers_take_the_costs_of_the_run(tmp_path):
    (tmp_path / "stub.py").write_text(STUB)
    stub = run.bench_model({"bench_model": "stub"}, tmp_path)
    view = _view(stub.costs({}))
    peak = peaks("TPU v5 lite")
    committed = sum(e - p0 for st in STEPS for p0, _, e in st.rows)
    assert run.reader("step_mfu")(view) == pytest.approx(
        100 * 11.0 * committed / (WINDOW_S * peak["bf16_flops_per_s"]))
    iters = sum(st.iters for st in STEPS)
    assert run.reader("lmhead_epilogue_roofline")(view) == pytest.approx(
        100 * iters * 19.0 * 32 * 32 / peak["bf16_flops_per_s"] / LMHEAD_S)


# -- the dense module -----------------------------------------------------------------

def test_dense_costs_are_the_hand_values():
    c = run.bench_model(CFG).costs(CFG)
    assert c.n_layers == 36
    dense = 6_171_394_048                   # test_costs_peaks.py, by hand
    assert c.positions_flops([0], [3]) == pytest.approx(
        3 * dense + 4 * 36 * 16 * 128 * 6)
    flops, nbytes = c.attention_call([0, 100], 32)
    keys = (32 * 33 / 2) + (32 * 100 + 32 * 33 / 2)
    assert flops == pytest.approx(4 * 16 * 128 * keys)
    assert nbytes == pytest.approx((2 * 2 * 32 * 16 * 128
                                    + 2 * (32 + 132) * 2 * 128) * 2)
    assert c.lmhead_call(1024) == (
        2 * 1024 * 2048 * 151936,
        (2048 * 151936 + 1024 * 2048) * 2 + 1024 * 8)


# a recorded profile's steps: (iterations, [(pos0, ell0, pos_end)] per row)
STEPS = [run.StepRec(3, [(0, 301, 96), (410, 411, 414), (1200, 1201, 1203)]),
         run.StepRec(2, [(96, 301, 160), (414, 415, 417), (1203, 1204, 1203)]),
         run.StepRec(1, []),
         run.StepRec(4, [(160, 301, 288), (2000, 2001, 2004)])]
WINDOW_S, ATTN_S, LMHEAD_S = 0.25, 0.031, 0.0365


def _view(costs):
    trace = SimpleNamespace(window_s=WINDOW_S,
                            op_s={"closed_call.13": ATTN_S, "body.9": LMHEAD_S})
    return run.RunView(spec={"config": CFG}, seconds=51.0, setup_s=0.0,
                       recs=[], steps=STEPS, counters=None, trace=trace,
                       kernel_ops={"closed_call.13": "paged_mixed_attention",
                                   "body.9": "lmhead_epilogue"},
                       max_batch=32, span=32, device_kind="TPU v5 lite",
                       profile=(0.0, 1.0), costs=costs)


def _before(name, view):
    """The readers' arithmetic as it stood when each built ``Shapes``."""
    s = dense_costs.Shapes.from_config(view.spec["config"])
    peak = peaks(view.device_kind)
    if name == "step_mfu":
        start = [p0 for st in view.steps for p0, _, _ in st.rows]
        end = [e for st in view.steps for _, _, e in st.rows]
        return 100.0 * dense_costs.positions_flops(s, start, end) / (
            view.trace.window_s * peak["bf16_flops_per_s"])
    if name == "lmhead_epilogue_roofline":
        iters = sum(st.iters for st in view.steps)
        least = iters * dense_costs.roofline_seconds(
            *dense_costs.lmhead_call(s, view.max_batch * view.span), peak)
        return 100.0 * least / LMHEAD_S
    T, least = view.span, 0.0
    for st in view.steps:
        if not st.rows:
            continue
        p0, e0, end = (np.array(x, np.int64) for x in zip(*st.rows))
        prompt = p0 < e0 - 1
        for i in range(st.iters):
            lb = np.where(prompt, np.minimum(p0 + i * T, e0 - 1), p0 + i)
            lb = np.minimum(lb, np.maximum(end, p0))
            least += s.n_layers * dense_costs.roofline_seconds(
                *dense_costs.attention_call(s, lb, T), peak)
    return 100.0 * least / ATTN_S


@pytest.mark.parametrize("name", ["step_mfu", "paged_mixed_attention_roofline",
                                  "lmhead_epilogue_roofline"])
def test_cost_readers_read_as_before(name):
    view = _view(run.bench_model(CFG).costs(CFG))
    got = run.reader(name)(view)
    assert got is not None and 0 < got < 100
    assert got == _before(name, view)


# -- counters --------------------------------------------------------------------------

def test_counters_pass_every_key_through():
    stats = {"emitted": 5.0, "live_iters": 4.0, "kv_reserved_page_iters": 9.0,
             "kv_committed_page_iters": 8.0, "expert_tokens": 3.0}
    loop = SimpleNamespace(eng=SimpleNamespace(speculation_stats=stats))
    got = run.LoadLoop._counters(loop)
    assert got == stats and got is not stats
