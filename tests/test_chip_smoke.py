"""`chip_smoke.py` rehearsed on the CPU, so a later PR cannot break the
chip check without a red tier-1 test.

The script refuses every platform but the TPU; only these tests satisfy
that check (by monkeypatching `REQUIRED_PLATFORM`) — no option or
environment variable of the script does. What they show is that the
phases run and fail as they should, never a device number."""

import json

import pandas as pd
import pytest

import chip_smoke


def test_refuses_the_cpu_before_loading_data(capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "load",
                        lambda *a, **k: pytest.fail("data was loaded"))
    assert chip_smoke.main(["--sf", "0.01"]) != 0
    cap = capsys.readouterr()
    assert '"ok"' not in cap.out
    assert "platform is 'cpu', need 'tpu'" in cap.err


def test_phases_pass_in_process_at_sf001(capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    assert chip_smoke.main(["--sf", "0.01"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"       # as JAX reports it
    body = "\n".join(lines[:-1])
    for name in chip_smoke.SMOKE_QUERIES:
        assert f"embedded {name}: path=fused" in body
    for name in chip_smoke.SERVED_QUERIES:
        assert f"served {name}: grpc=" in body
    assert "acknowledged inserts read back" in body
    assert '"status": "GOOD"' in body and "native.available()=True" in body


def test_oracle_mismatch_exits_nonzero(capsys, monkeypatch):
    import tests.tpch_util as tu
    real = tu.oracle

    def wrong(name, data):
        want = real(name, data)
        if name == "q6":
            want = want * 1.001 if not isinstance(want, pd.DataFrame) \
                else want.apply(lambda c: c * 1.001)
        return want

    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(tu, "oracle", wrong)
    assert chip_smoke.main(["--sf", "0.01"]) != 0
    cap = capsys.readouterr()
    assert '"ok"' not in cap.out
    assert "embedded q1:" in cap.out and "embedded q3:" not in cap.out
    assert "FAILED" in cap.err


def test_mesh_phase_needs_its_devices(capsys, monkeypatch):
    """`--chips 4` never passes on fewer than four devices of the
    required platform (the suite's virtual CPU mesh has eight)."""
    import jax
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:2])
    monkeypatch.setattr(chip_smoke, "mesh_phase",
                        lambda *a, **k: pytest.fail("mesh phase ran"))
    assert chip_smoke.main(["--sf", "0.01", "--chips", "4"]) != 0
    cap = capsys.readouterr()
    assert '"ok"' not in cap.out and "need 4" in cap.err


def test_mesh_phase_on_four_virtual_devices(capsys, monkeypatch):
    import jax
    four = jax.local_devices()[:4]
    assert len(four) == 4
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(jax, "devices", lambda *a: four)
    assert chip_smoke.main(["--sf", "0.01", "--chips", "4"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1])["device"]["count"] == 4
    body = "\n".join(lines[:-1])
    assert "mesh q1: path=distributed " in body
    assert "mesh shuffle-join: path=distributed-shuffle-join" in body
    assert "embedded" not in body and "served" not in body


def test_dryrun_multichip_never_falls_back_silently(monkeypatch):
    """Too few devices and no CPU request is an error — the entry point
    provisions a virtual CPU mesh only for a caller that asked for the
    CPU (`JAX_PLATFORMS=cpu`)."""
    import jax

    import __graft_entry__ as graft
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    monkeypatch.setattr(graft, "_dryrun_multichip_inproc",
                        lambda n: pytest.fail("ran on too few devices"))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="has 1 device"):
        graft.dryrun_multichip(4)
