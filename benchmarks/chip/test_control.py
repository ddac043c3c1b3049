"""``correct`` has to come out false where the timed path is wrong.

On the CPU at small widths: the control (the reference in fp8, the precision
below the configuration's bfloat16, read at the program's own tokens) fails
each serving cell's limit, the training control (the fp8 reference's steps
in place of the program's) fails one of the training cell's, and each fault that a cell can have, planted
under the timed path, makes a whole run report ``correct: false``:

- serving: a token altered where it is produced (the decode step's logits
  shifted by one id);
- training: a step that returns its state unchanged; half of the batch left
  out, the mean taken over the rest (at the small size's B=2: the cell's
  own batch of 1 cannot have this fault).
"""
import dataclasses
import json

import jax
import pytest

import harness
import smoke

SERVE = ["olmo-1b.serve.chat", "rwkv6-1.6b.serve.longprompt"]


def run_cell(name, monkeypatch, capsys):
    import run

    smoke.use(harness, monkeypatch)
    assert run.main(["--workload", name, "--seed", "4242", "--seconds", "1"],
                    devices=jax.devices()[:1]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# The fp8 control's widest gap grows with depth, width and the tokens read:
# at the smallest widths it swings around the cells' limits (a few dozen
# tokens), so this test runs at widths of 1024 and 128 tokens read (about
# 20 s for OLMo and 80 s for RWKV on the CPU; the cells themselves read
# 0.80 and 3.0 and more on the chip, PERF.md).
CONTROL_SIZES = {
    "olmo-1b.serve.chat": (
        dict(num_hidden_layers=8, hidden_size=1024, num_attention_heads=8,
             num_key_value_heads=8, head_dim=128, intermediate_size=4096, vocab_size=16384),
        dict(batch=8, prompt_len=256, new_tokens=16, check_sequences=8, check_block=4)),
    "rwkv6-1.6b.serve.longprompt": (
        dict(num_hidden_layers=12, hidden_size=1024, num_attention_heads=16,
             num_key_value_heads=16, head_size=64, intermediate_size=3584,
             vocab_size=16384),
        dict(batch=8, prompt_len=512, new_tokens=16, check_sequences=8, check_block=4)),
}


@pytest.mark.parametrize("name", SERVE)
def test_serving_control_fails_the_limit(name):
    cell = harness.cell(name)
    config, traffic = CONTROL_SIZES[name]
    cell = dataclasses.replace(cell, config=dict(cell.config, **config),
                               traffic=dict(cell.traffic, **traffic))
    run = harness.Run(cell, 17, 0.0, False, jax.devices()[:1])  # one batch
    driver = harness.load_module("drivers", cell.traffic["driver"]).Driver(run)
    driver.setup()
    driver.window()
    r = driver.readings(control=True)
    assert r["max_gap"] <= cell.limits["max_gap"]["limit"] < r["control_gap"]


def test_train_control_fails_the_limits():
    """The fp8 reference in the program's place: its first three steps, in
    place of the program's, go through the cell's own checks and limits."""
    cell = smoke.shrink(harness.cell("olmo-1b.train.s2048"))
    cell = dataclasses.replace(
        cell, traffic=dict(cell.traffic, seq_len=128),
        config=dict(cell.config, num_hidden_layers=4, hidden_size=256, num_attention_heads=4,
                    num_key_value_heads=4, head_dim=64, intermediate_size=1024,
                    vocab_size=4096))
    run = harness.Run(cell, 17, 0.0, False, jax.devices()[:1])
    driver = harness.load_module("drivers", "train").Driver(run)
    driver.setup()
    control = driver._reference("fp8")
    assert all(c.ok for c in driver.checks())
    driver.first = control
    failed = [c.name for c in driver.checks() if not c.ok]
    assert failed, "the fp8 control passed every limit"


@pytest.mark.parametrize("name", SERVE)
def test_serving_token_altered(name, monkeypatch, capsys):
    from repro.models import decode

    real = decode.decode_step

    def shifted(*a, **kw):
        logits, caches = real(*a, **kw)
        return jax.numpy.roll(logits, 1, axis=-1), caches

    monkeypatch.setattr(decode, "decode_step", shifted)
    out = run_cell(name, monkeypatch, capsys)
    assert out["correct"] is False and out["checks"]["max_gap"]["value"] > 0


def test_train_state_unchanged(monkeypatch, capsys):
    from repro.models import steps

    real = steps.train_step

    def frozen(cfg, run, params, opt, batch, **kw):
        return params, opt, real(cfg, run, params, opt, batch, **kw)[2]

    monkeypatch.setattr(steps, "train_step", frozen)
    out = run_cell("olmo-1b.train.s2048", monkeypatch, capsys)
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch(monkeypatch, capsys):
    from repro.models import steps

    real = steps.train_step

    def half(cfg, run, params, opt, batch, **kw):
        tokens = batch["tokens"]
        return real(cfg, run, params, opt, {"tokens": tokens[: tokens.shape[0] // 2]}, **kw)

    monkeypatch.setattr(steps, "train_step", half)
    out = run_cell("olmo-1b.train.s2048", monkeypatch, capsys)
    assert out["correct"] is False
