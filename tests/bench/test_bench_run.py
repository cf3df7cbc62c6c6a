"""The rest of a run, without the harness's look for a chip, at a tiny size on
the CPU: a sound run comes out correct, the controls (the reference in the
precision below the configuration's; the program with its own lower-precision
path switched on) do not, and neither does a run whose timed path is broken
underneath."""

import json

import pytest

from bench import run as bench_run


def _run(capsys, root, *argv):
    rc = bench_run.main(list(argv) + ["--root", root])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return rc, lines[-1], {l["phase"]: l for l in lines[:-1] if "phase" in l}


def _check_line(line, metrics):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == set(metrics)
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}


def test_serving_run_sound_then_control(capsys, tiny_root):
    rc, line, phases = _run(capsys, tiny_root, "--workload", "tiny-serve.tinychat", "--seed", "3000000019",
                            "--seconds", "5", "--trace", "0", "--control", "int8")
    assert rc == 0
    _check_line(line, ["ttft_p90_ms", "tpot_mean_ms", "serve_tokens_per_s", "setup_s"])
    check, window = phases["check"], phases["window"]
    assert line["correct"] is True, check["reasons"]
    assert line["attempted"] == window["attempted"] == 15 and line["failed"] == 0
    assert window["window_compiles"] == 0
    # every request the window finished is compared, token by token
    assert check["sequences"] == 15 and check["served_tokens"] == window["output_tokens"]["sum"]
    assert all(check["compared"][k] <= check["limits"][k] for k in check["limits"])
    assert set(check["limits"]) == {"served_token_gap", "served_token_gap_mean"}
    # the control: the int8 reference's first choices lie further below the best than the limits allow
    assert all(check["control"][k] > 3 * check["limits"][k] for k in check["limits"])
    assert window["tpot_mean_ms"] > 0 and window["tpot_p90_ms"]["n"] == 15


def test_serving_run_of_the_program_s_own_lower_precision_is_not_correct(capsys, tiny_root):
    rc, line, phases = _run(capsys, tiny_root, "--workload", "tiny-serve.tinychat", "--seed", "11",
                            "--seconds", "4", "--trace", "0", "--control", "program")
    assert rc == 0 and line["correct"] is False and line["failed"] == 0
    check = phases["check"]
    assert any(check["compared"][k] > check["limits"][k] for k in check["limits"]), check["compared"]


def test_serving_run_with_a_token_altered_is_not_correct(capsys, tiny_root, monkeypatch):
    from paddlenlp_tpu.experimental.engine import InferenceEngine

    emit = InferenceEngine._emit

    def altered(self, req, tok):
        # one token of each answer changed where the engine hands it to the stream
        return emit(self, req, (tok + 1) % 2048 if len(req.output_ids) == 3 else tok)

    monkeypatch.setattr(InferenceEngine, "_emit", altered)
    rc, line, phases = _run(capsys, tiny_root, "--workload", "tiny-serve.tinychat", "--seed", "5",
                            "--seconds", "4", "--trace", "0")
    assert rc == 0 and line["correct"] is False
    assert phases["check"]["compared"]["served_token_gap"] > phases["check"]["limits"]["served_token_gap"]
    assert line["failed"] == 0  # every request still answered in full: only the comparison sees it


def test_training_run_sound_then_control(capsys, tiny_root):
    rc, line, phases = _run(capsys, tiny_root, "--workload", "tiny-train.tinyseq", "--seed", "3000000019",
                            "--seconds", "2", "--trace", "0", "--control", "bfloat16")
    assert rc == 0
    _check_line(line, ["train_tokens_per_s", "setup_s"])
    check = phases["check"]
    assert line["correct"] is True, (check["reasons"], check.get("rows_checked"))
    assert all(check["compared"][k] <= check["limits"][k] for k in check["limits"])
    # the control fails one of the cell's numbers: norm scales cannot take the step in bfloat16
    assert check["control"]["param_delta_gap"] > check["limits"]["param_delta_gap"]


def test_training_run_whose_step_returns_its_state_is_not_correct(capsys, tiny_root, monkeypatch):
    from paddlenlp_tpu.trainer import Trainer

    build = Trainer._build_train_step

    def broken(self):
        step = build(self)

        def unchanged(state, batch, rng):
            _, metrics = step(jax_copy(state), batch, rng)
            return state, metrics

        return unchanged

    import jax

    jax_copy = lambda tree: jax.tree.map(lambda x: x.copy(), tree)  # the real step donates its input
    monkeypatch.setattr(Trainer, "_build_train_step", broken)
    rc, line, phases = _run(capsys, tiny_root, "--workload", "tiny-train.tinyseq", "--seed", "7",
                            "--seconds", "2", "--trace", "0")
    assert rc == 0 and line["correct"] is False
    assert phases["check"]["compared"]["param_delta_gap"] > phases["check"]["limits"]["param_delta_gap"]


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "qwen2-1.5b-serve.chat", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code == 2
    out = capsys.readouterr()
    assert not [l for l in out.out.splitlines() if l.startswith('{"correct"')]
    assert "needs 1 TPU chip" in out.err
