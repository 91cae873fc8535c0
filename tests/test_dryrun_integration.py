"""Dry-run integration: one small cell lowers + compiles on the forced
512-device mesh in a subprocess (the deliverable-(e) contract), and the
collective-bytes parser handles both replica-group formats."""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# XLA's 512-device compile time varies by an order of magnitude across
# hosts; a fixed deadline flakes tier-1 on slow CI shards.  The budget
# comes from the environment (override upward on known-slow machines)
# and exhausting it skips rather than fails — a timeout says nothing
# about the dryrun contract, only about this host's compile throughput.
# The test's own time limit stays a minute above the budget.
DRYRUN_BUDGET_S = float(os.environ.get("MAGNETON_DRYRUN_BUDGET_S", "240"))


@pytest.mark.slow
@pytest.mark.timeout(DRYRUN_BUDGET_S + 60)
def test_dryrun_cell_compiles_on_512_devices():
    with tempfile.TemporaryDirectory() as out:
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)          # dryrun must set it itself
        try:
            r = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", "gpt2-small", "--shape", "decode_32k",
                 "--mesh", "both", "--out", out],
                env=env, capture_output=True, text=True,
                timeout=DRYRUN_BUDGET_S)
        except subprocess.TimeoutExpired:
            pytest.skip(f"dryrun exceeded the {DRYRUN_BUDGET_S:g}s compile "
                        "budget (set MAGNETON_DRYRUN_BUDGET_S to raise it)")
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        cells = sorted(os.listdir(out))
        assert len(cells) == 2
        for c in cells:
            rec = json.load(open(os.path.join(out, c)))
            assert rec["status"] == "ok", rec.get("error")
            assert rec["devices"] in (256, 512)
            assert rec["per_device"]["flops"] > 0
            assert rec["roofline"]["dominant"] in (
                "compute_s", "memory_s", "collective_s")


def test_collective_parser_explicit_groups():
    from repro.launch.dryrun import collective_bytes
    hlo = ("%ar = f32[1024,256]{1,0} all-reduce(%x), "
           "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add")
    out = collective_bytes(hlo, pod_size=256)
    want = 2 * 1024 * 256 * 4 * 3 / 4
    assert out["ici"] == pytest.approx(want)
    assert out["dcn"] == 0


def test_collective_parser_iota_groups_pod_crossing():
    from repro.launch.dryrun import collective_bytes
    # 16 groups of 32, iota over [2,16,16] transposed so groups span pods
    hlo = ("%ag = bf16[64,64]{1,0} all-gather(%x), "
           "replica_groups=[16,32]<=[2,16,16]T(1,0,2), dimensions={0}")
    out = collective_bytes(hlo, pod_size=256)
    assert out["dcn"] > 0          # groups mix pod 0 and pod 1 ids
    assert out["ici"] == 0


def test_collective_parser_variadic_tuple_result():
    from repro.launch.dryrun import collective_bytes
    hlo = ("%ar = (f32[128]{0}, f32[256]{0}) all-reduce(%a, %b), "
           "replica_groups={{0,1}}, to_apply=%add")
    out = collective_bytes(hlo, pod_size=256)
    want = 2 * (128 + 256) * 4 * 1 / 2
    assert out["ici"] == pytest.approx(want)
