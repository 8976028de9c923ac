"""What ``correct`` is worth, at a size a test run can hold: with limits set
for that size as the cells' own are set a sound tiny run passes, the control (the plain reference computed
in fp8, the precision below the configuration's bf16) fails, and so does a
run whose timed path is broken underneath: a step that leaves its state
unchanged, half of the batch left out with the mean taken over the rest, a
token altered where it is produced."""

import pytest

from perfbench_tiny import (CLOSED, SEED, SERVE_CELL, TRAIN, TRAIN_CELL,
                            tiny_cell)

from benchmark import run as R
from benchmark.lib import correct, system, traffic, weights


def _run(cell):
    return R.run_cell(cell, SEED, 1.0, False, require_chip=False)


def test_sound_train_run_is_correct():
    res = _run(tiny_cell(TRAIN, TRAIN_CELL))
    assert res["correct"] is True, res["compared"]


def test_fp8_control_fails_the_training_limits():
    cell = tiny_cell(TRAIN, TRAIN_CELL)
    pool = traffic.train_batches(cell.mix, cell.cfg["vocab_size"], SEED)
    mesh = system.build_mesh(None, 1)
    ref = R.reference_three(cell, SEED, pool, mesh)
    assert correct.control_mode(cell.cfg) == "float8"
    low = R.reference_three(cell, SEED, pool, mesh, "float8")
    ok, shown = correct.judge(correct.train_numbers(low, ref),
                              cell.extra["limits"])
    assert ok is False, shown


def test_unchanged_state_is_not_correct(monkeypatch):
    real = system.build_train_step

    def frozen(fam, cfg, weights, opt_cfg, mesh):
        return real(fam, cfg, weights, dict(opt_cfg, learning_rate=0.0), mesh)
    monkeypatch.setattr(system, "build_train_step", frozen)
    res = _run(tiny_cell(TRAIN, TRAIN_CELL))
    assert res["correct"] is False
    assert res["compared"]["delta3_leaf_gap"]["value"] == pytest.approx(1.0)


def test_half_a_batch_is_not_correct(monkeypatch):
    from paddle_tpu.framework.functional import functional_call

    def half(model, params, batch):
        ids, labels = batch
        n = ids.shape[0] // 2
        return functional_call(model, params, ids[:n], labels[:n],
                               training=True)
    cell = tiny_cell(TRAIN, TRAIN_CELL)
    monkeypatch.setattr(cell.family.adapter, "loss_fn", half)
    res = _run(cell)
    assert res["correct"] is False, res["compared"]


def _served_to_the_end(cell, n_requests):
    """``n_requests`` of the mix served until the engine is idle: the same
    requests and tokens on every run, where a timed window on a shared CPU
    finishes now these, now those."""
    fam = cell.family
    eng = fam.adapter.build_engine(
        cell.cfg, weights.make_weights(fam.weights, cell.cfg, SEED),
        cell.mix["engine"])
    stream = traffic.request_stream(cell.mix, cell.cfg["vocab_size"], SEED)
    served = []
    for i in range(n_requests):
        prompt, want = next(stream)
        served.append((prompt, eng.submit(
            system.make_request(f"q{i}", prompt, want))))
    while eng.sched.n_pending:
        eng.step()
    return {"finished": [{"rid": s.rid, "prompt": p,
                          "tokens": list(s.out_tokens)} for p, s in served]}


def test_sound_serving_is_correct_and_its_fp8_control_is_not():
    cell = tiny_cell(CLOSED, SERVE_CELL)
    cell.extra["check"]["sample"] = 16
    rec = _served_to_the_end(cell, 16)
    ok, shown = correct.judge(R.check_serve(cell, SEED, rec),
                              cell.extra["limits"])
    assert ok is True, shown
    ok, shown = correct.judge(R.check_serve(cell, SEED, rec, "float8"),
                              cell.extra["limits"])
    assert ok is False, shown


def test_sound_serve_run_is_correct():
    res = _run(tiny_cell(CLOSED, SERVE_CELL))
    assert res["correct"] is True, res["compared"]


def test_an_altered_token_is_not_correct(monkeypatch):
    cell = tiny_cell(CLOSED, SERVE_CELL)
    real = cell.family.adapter.build_engine

    def tampering(cfg, weights, eng_cfg):
        eng = real(cfg, weights, eng_cfg)
        decode = eng._decode_fn

        def altered(*args):
            tok, k, v = decode(*args)
            return (tok + 1) % cfg["vocab_size"], k, v
        eng._decode_fn = altered
        return eng
    monkeypatch.setattr(cell.family.adapter, "build_engine", tampering)
    res = _run(cell)
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("mix,like", [(TRAIN, TRAIN_CELL),
                                      (CLOSED, SERVE_CELL)])
def test_limits_readings_say_program_control_and_faults(mix, like, capsys):
    """``benchmark/limits.py`` at a tiny size: one line a reading, the
    control's mode taken from the configuration's precision."""
    import json
    from benchmark import limits
    cell = tiny_cell(mix, like)
    if mix["kind"] == "train_steps":
        limits.train_readings(cell, [SEED, SEED + 1], 1)
        want = ["program", "control:float8", "fault:half_batch",
                "fault:state_unchanged", "program"]
    else:
        limits.serve_readings(cell, [SEED], 1, 1.0)
        want = ["program", "control:float8"]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["what"] for x in lines] == want
    assert all(x["workload"] == cell.name and x["numbers"] for x in lines)
    if mix["kind"] == "train_steps":
        assert lines[3]["numbers"]["delta3_leaf_gap"]["value"] == \
            pytest.approx(1.0)
