"""CPU rehearsal of ``chip_smoke.py``: the train and serve phase functions
at toy width (the tests/test_serving.py CFG), so the control flow and every
gate that does not need a chip is exercised here before chip time is spent —
and the script's refusal to run without a TPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

CFG = dict(vocab_size=48, d_model=16, n_layer=2, n_head=2, d_inner=32,
           max_pos=64, dropout=0.0)


def test_train_then_serve_phases_at_toy_width():
    cfg = T.BertConfig(**CFG)
    meter = chip_smoke.CompileMeter()
    train, scope = chip_smoke.phase_train(
        cfg, seq_len=16, batch=4, steps=5, place=None, on_chip=False,
        meter=meter)
    assert train["ok"] and len(train["losses"]) == 6
    assert train["losses"][-1] < train["losses"][0]
    assert train["compile_s"] > 0            # the meter saw the compiles

    serve = chip_smoke.phase_serve(
        cfg, scope, prompt_lens=(3, 5, 6, 8), max_new=4, page_len=4,
        on_chip=False, meter=meter)
    assert serve == {**serve, "ok": True, "requests": 4, "tokens": 16,
                     "trace_count": 1, "pages_in_use": 0}
    # float32 on CPU: the engine's tokens ARE the full-context argmax
    assert serve["max_argmax_gap_std"] == 0.0


@pytest.mark.slow
def test_multichip_phase_on_the_virtual_mesh():
    """Both layouts over the 8-device CPU mesh: every parameter on all
    devices, mp-split weights under GSPMD only, batch-sharded fetch."""
    import jax
    cfg = T.BertConfig(vocab_size=64, d_model=16, n_layer=2, n_head=4,
                       d_inner=32, max_pos=32, dropout=0.0)
    n = len(jax.devices())
    rep = chip_smoke.phase_multichip(cfg, 8, n, None, False,
                                     chip_smoke.CompileMeter())
    assert rep["data_parallel"]["params_split"] == 0
    assert rep["gspmd_dp2_mp2"]["params_split"] > 0
    assert rep["data_parallel"]["fetch_devices"] == n


def test_a_failed_gate_raises():
    with pytest.raises(AssertionError, match="loss did not fall"):
        chip_smoke.check(False, "loss did not fall: [1.0, 2.0]")


def test_result_line_holds_exactly_the_keys_the_chip_check_parses():
    """The driver refuses the PR unless the LAST stdout line is an object
    with exactly "ok" and "device", the latter exactly platform/kind/count."""
    import json
    res = json.loads(chip_smoke.result_line(chip_smoke.device_identity()))
    assert set(res) == {"ok", "device"} and res["ok"] is True
    dev = res["device"]
    assert set(dev) == {"platform", "kind", "count"}
    assert dev["platform"] == "cpu" and isinstance(dev["kind"], str)
    assert type(dev["count"]) is int and dev["count"] >= 1


def test_main_ends_with_the_result_line(monkeypatch, capsys):
    """main() with the backend check and the phases stubbed out: the summary
    line carries the details, and nothing follows the result line."""
    import json
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(chip_smoke, "phase_train",
                        lambda *a: ({"ok": True}, None))
    monkeypatch.setattr(chip_smoke, "phase_serve", lambda *a: {"ok": True})
    monkeypatch.setattr(chip_smoke, "MULTICHIP_MIN_DEVICES", 10 ** 6)
    chip_smoke.main()
    lines = capsys.readouterr().out.splitlines()
    assert lines[-2].startswith("chip_smoke: summary {")
    summary = json.loads(lines[-2][len("chip_smoke: summary "):])
    assert summary["phases"]["multichip"].startswith("not run (")
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert lines[-1] == chip_smoke.result_line(chip_smoke.device_identity())


def test_main_refuses_to_run_without_a_tpu(capsys):
    """Under JAX_PLATFORMS=cpu: non-zero exit, a message naming the missing
    TPU, the device line printed first, and no JSON result."""
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "no TPU found" in str(exc.value.code)
    out = capsys.readouterr().out
    assert out.startswith("chip_smoke: platform=cpu")
    assert '"ok"' not in out
