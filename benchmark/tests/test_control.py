"""The control of each kind of cell comes out not correct
(`benchmark/control.py`, at rehearsal sizes on the CPU)."""

import json

import jax
import pytest

from benchmark import control, run


@pytest.mark.parametrize("workload", ["table20-lb2", "table20-lb1",
                                      "serve-steady"])
def test_control_is_not_correct(capsys, monkeypatch, workload):
    from tpu_tree_search.engine import device
    from tpu_tree_search.service import spool

    # control.main switches these; put them back after the test
    monkeypatch.setattr(run.Run, "setup_done", run.Run.setup_done)
    monkeypatch.setattr(device, "search", device.search)
    monkeypatch.setattr(spool, "request_from_payload",
                        spool.request_from_payload)
    jax.clear_caches()
    rc = control.main(["--workload", workload, "--seed", "4099",
                       "--seconds", "3", "--trace", "0", "--rehearse"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["failed"] > 0
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
