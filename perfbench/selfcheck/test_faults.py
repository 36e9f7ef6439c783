"""`correct` comes out false when the timed path is broken underneath,
and when the control (the reference in float8) stands in the program's
place. The look for a chip is skipped; the rest of a run is driven as
it is on the chip, at toy size.

Faults planted (each one that a cell of this benchmark can have):
  training   a step that returns its state unchanged; half of the batch
             left out, the mean taken over the rest; the exchange
             between chips left out (each chip keeps its own shard's
             gradient: chip 0's parameters then follow a quarter batch)
  serving    a token altered where it is produced
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.selfcheck import rehearse as rh  # noqa: E402

rh.pin_cpu()
from perfbench.harness import check, common  # noqa: E402


def _only_rows(monkeypatch, share):
    """Feed the step `share` of its batch, repeated to fill the shape:
    every mean and BatchNorm statistic is then that of the rows kept."""
    from mxnet_tpu.module.module import Module

    orig = Module._stage_for_fused

    def staged(self, data_batch):
        vals = orig(self, data_batch)
        if vals is None:
            return None
        import jax.numpy as jnp

        out = {}
        for k, v in vals.items():
            keep = v.shape[0] // share
            out[k] = jnp.concatenate([v[:keep]] * share, axis=0)
        return out

    monkeypatch.setattr(Module, "_stage_for_fused", staged)


def test_training_is_correct_when_nothing_is_broken():
    line, _ = rh.rehearse("resnet50_b256_synth")
    assert line["correct"] is True, line["checks"]


def test_a_step_that_returns_its_state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.dp_step import FusedTrainStep

    orig = FusedTrainStep.step

    def frozen(self, data_vals):
        keep = jax.tree_util.tree_map(
            jnp.copy, (self.params, self.states, self.auxs))
        outs = orig(self, data_vals)
        self.params, self.states, self.auxs = keep
        return outs

    monkeypatch.setattr(FusedTrainStep, "step", frozen)
    line, _ = rh.rehearse("resnet50_b256_synth")
    assert line["correct"] is False
    value, limit = line["checks"]["change3_median_gap"]
    assert value > limit and abs(value - 1.0) < 1e-6


def test_half_of_the_batch_left_out(monkeypatch):
    _only_rows(monkeypatch, 2)
    line, _ = rh.rehearse("resnet50_b256_synth")
    assert line["correct"] is False, line["checks"]


def test_the_exchange_between_chips_left_out(monkeypatch):
    cells = {w["name"] for w in common.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["workloads"]}
    if "resnet50_dp4_b1024_synth" not in cells:
        pytest.skip("no four-chip cell in BENCHMARK.json")
    _only_rows(monkeypatch, 4)
    line, _ = rh.rehearse("resnet50_dp4_b1024_synth")
    assert line["correct"] is False, line["checks"]


def test_a_token_altered_where_it_is_produced(monkeypatch):
    from mxnet_tpu.decoding.engine import DecodeEngine

    orig = DecodeEngine.step
    calls = {"n": 0}

    def altered(self, tokens, *a, **kw):
        out = np.array(orig(self, tokens, *a, **kw))
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            out[:] = (out + 1) % self.cfg.vocab
        return out

    monkeypatch.setattr(DecodeEngine, "step", altered)
    line, _ = rh.rehearse("opt1p3b_chat_closed")
    assert line["correct"] is False
    value, limit = line["checks"]["served_logit_gap"]
    assert value > limit


def test_serving_is_correct_when_nothing_is_broken():
    line, _ = rh.rehearse("opt1p3b_chat_closed")
    assert line["correct"] is True, line["checks"]


def test_the_training_control_comes_out_not_correct():
    """The reference in float8, put in the program's place, against the
    reference: beyond the limits."""
    import jax.numpy as jnp

    cfg = common.load_json(os.path.join(
        ROOT, "perfbench/selfcheck/tiny/resnet50.json"))
    ref = common.load_py(os.path.join(
        ROOT, "perfbench/references/resnet50.py"), "ref_resnet50")
    w0 = ref.make_params(7, cfg)
    pool = ref.make_batches(7, cfg, 8, 3)
    hyper = cfg["optimizer"]
    want = ref.train_steps(w0, pool, cfg, hyper, compute=jnp.float32)
    ctrl = ref.train_steps(w0, pool, cfg, hyper, compute=jnp.float32,
                           quant="fp8")
    w0h = {k: np.asarray(v) for k, v in w0.items()}
    numbers, _ = check.training_numbers(
        {"losses": ctrl["losses"], "grad1": ctrl["grad1"],
         "w3": ctrl["params"], "probs1": ctrl["probs1"]}, want, w0h, hyper,
        ref._decays)
    _, ok = check.judge(numbers, cfg["check"]["limits"])
    assert not ok, numbers


def test_the_serving_control_comes_out_not_correct():
    """At each position of a prompt and its greedy tokens, the token the
    float8 forward puts first lies below the reference's best by more
    than the limit somewhere."""
    import jax.numpy as jnp

    cfg = common.load_json(os.path.join(
        ROOT, "perfbench/selfcheck/tiny/opt_1p3b.json"))
    ref = common.load_py(os.path.join(
        ROOT, "perfbench/references/opt_1p3b.py"), "ref_opt")
    params = ref.make_params(3, cfg, jnp.float32)
    rs = np.random.RandomState(3)
    worst = 0.0
    for _ in range(4):
        prompt = rs.randint(2, cfg["vocab_size"], 24).tolist()
        served = []
        for _ in range(24):       # the reference's own greedy tokens
            lg = ref.logits(params, jnp.asarray(prompt + served,
                                                jnp.int32), cfg)
            served.append(int(jnp.argmax(lg[-1])))
        gap, low = ref.served_gaps(params, prompt, served, cfg, pad_to=64,
                                   control=True)
        assert float(gap.max()) <= cfg["check"]["limits"][
            "served_logit_gap"]
        worst = max(worst, float(low.max()))
    assert worst > cfg["check"]["limits"]["served_logit_gap"], worst
