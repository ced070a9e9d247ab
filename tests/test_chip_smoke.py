"""chip_smoke.py and bench.py on a machine without the chip.

The smoke's stages are rehearsed on the CPU at a few thousand rows through
`run(plan)`'s argument (the sizes and the engines the platform resolves),
and both scripts must refuse, loudly and without a result, to run their
main path on anything but a TPU.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_every_stage_rehearses_on_the_cpu(capsys):
    plan = dict(chip_smoke.FLAGSHIP,
                params=dict(chip_smoke.FLAGSHIP["params"], num_leaves=15),
                n_train=6000, n_test=2000, n_kernel=4096, iters=5,
                kernel_impl="auto",
                engines={"histogram": "lax", "partition": "lax"})
    out = chip_smoke.run(plan, require_tpu=False)
    assert out["ok"] is True and out["claim"] is None
    assert out["device"]["platform"] == "cpu"
    st = out["stages"]
    assert st["train"]["fast_path"] is True
    assert st["train"]["engines"] == plan["engines"]
    assert st["train"]["compiles_in_last_two_iterations"] == 0
    assert st["train"]["binning"]["path"] in ("native", "python")
    assert st["serve"]["served_by"] == ["device"]
    assert st["serve"]["degradations"] == 0
    assert all(t["common_regions"] == max(t["leaves"])
               for t in st["kernel"]["trees"])
    # conftest provisions 8 virtual devices, so the mesh stage runs
    assert st["mesh"]["parallel_mode"] == "data"
    assert st["mesh"]["payload_devices"] == [0, 1, 2, 3]
    assert st["mesh"]["full_shape"]["payload_devices"] == [0, 1, 2, 3]
    assert st["mesh"]["full_shape"]["rows"] == plan["n_train"]
    assert json.dumps(out)      # the summary line is JSON-serializable
    # the last stdout line is the driver's: these keys and no others
    result = json.loads(chip_smoke.result_line(out))
    assert list(result) == ["ok", "device"] and result["ok"] is True
    assert list(result["device"]) == ["platform", "kind", "count"]
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int


def _run_script(name):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, name)], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    r = _run_script("chip_smoke.py")
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr and "not tpu" in r.stderr
    assert '"ok"' not in r.stdout, "no result line without the chip"


def test_bench_refuses_the_cpu():
    """No CPU re-exec, no shrunk problem, no borrowed TPU number."""
    r = _run_script("bench.py")
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr and "not tpu" in r.stderr
    assert r.stdout.strip() == ""
