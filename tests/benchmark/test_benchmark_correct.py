"""`correct` has been shown to fail: the harness, driven end to end at
a size a test can hold with the stand-in provider in the device's
place, reads `correct` true on a sound run and false for each control
(a reference with one stated guarantee broken, put in the program's
place) and for each fault planted under the timed path."""

import argparse
import json
import os

import pytest

from benchmark import run, standin
from fabric_tpu import native
from fabric_tpu.peer.peer import Channel

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason="the native block-prep library cannot be built here")


def tiny_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = manifest["workloads"][0]
    with open(os.path.join(ROOT, manifest["configs"][0]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    # a size a test can hold: 48-transaction blocks over 200 keys (five
    # blocks create them), a tenth tampered so every kind occurs in a
    # handful of blocks
    config["orderer"]["BatchSize"]["MaxMessageCount"] = 48
    traffic["loop"] = {"kind": "closed", "supply_tx_per_s": 6000}
    traffic["warmup_blocks"] = 1
    traffic["transactions"].update(keys=200, tampered_share=0.1)
    return manifest, cell, config, traffic


def drive(seed=2 ** 31 + 5, control="", trace=0):
    manifest, cell, config, traffic = tiny_cell()
    args = argparse.Namespace(
        workload=cell["name"], seed=seed, seconds=0.3, trace=trace,
        control=control, rehearse=True, workers=0)
    rc, result = run.execute(manifest, cell, config, traffic, args)
    assert rc == 4, "a rehearsal is never a pass"
    return result


def test_a_sound_run_is_correct():
    r = drive()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert all(v["value"] == 0 for v in r["compared"].values())
    assert list(r)[-1] == "compared"
    assert r["info"]["signatures_reverified"] > 0


def test_a_run_says_how_much_of_its_supply_it_used():
    r = drive(seed=2 ** 31 + 35)
    info = r["info"]
    # 6,000 tx/s of 48-transaction blocks over 0.3 s
    assert info["supply_blocks"] == run.blocks_needed(
        tiny_cell()[3], 48, 0.3) == 39
    assert info["window_blocks"] == r["attempted"] >= 2
    assert info["supply_used_share"] == \
        info["window_blocks"] / info["supply_blocks"]
    assert 0 < info["supply_used_share"] <= 1
    assert list(r)[-2:] == ["info", "compared"]


@pytest.mark.parametrize("control", run.CONTROLS)
def test_the_control_is_not_correct(control):
    r = drive(control=control)
    assert r["correct"] is False
    assert r["compared"][f"control.{control}.flag_mismatches"]["value"] > 0
    # the program itself, in the same run, still reads sound
    assert r["compared"]["flag_mismatches"]["value"] == 0


def test_both_controls_in_one_run_each_fail():
    r = drive(control=",".join(run.CONTROLS))
    assert r["correct"] is False
    for c in run.CONTROLS:
        assert r["compared"][f"control.{c}.flag_mismatches"]["value"] > 0
    assert r["compared"]["control.skip_mvcc.state_mismatches"]["value"] >= 0


def test_fault_a_verdict_altered_where_it_is_produced(monkeypatch):
    start = standin.StandInProvider.verify_prepared_start

    def flipped(self, *a, **kw):
        resolve = start(self, *a, **kw)

        def altered():
            out = list(resolve())
            out[len(out) // 2] = not out[len(out) // 2]
            return out
        return altered
    monkeypatch.setattr(standin.StandInProvider, "verify_prepared_start",
                        flipped)
    r = drive()
    assert r["correct"] is False
    assert r["compared"]["flag_mismatches"]["value"] > 0


def test_fault_half_of_the_batch_left_out(monkeypatch):
    start = standin.StandInProvider.verify_prepared_start

    def half(self, digests, r, rpn, w, der_ok, key_idx, keys, get_sig):
        n = len(der_ok) // 2
        resolve = start(self, digests[:n], r[:n], rpn[:n], w[:n],
                        der_ok[:n], key_idx[:n], keys, get_sig)
        return lambda: list(resolve()) + [True] * (len(der_ok) - n)
    monkeypatch.setattr(standin.StandInProvider, "verify_prepared_start",
                        half)
    r = drive()
    assert r["correct"] is False
    assert r["compared"]["flag_mismatches"]["value"] > 0


def test_fault_a_commit_that_leaves_the_state_unchanged(monkeypatch):
    def no_commit(self, block, flags, rwsets=None, tx_ids=None):
        return list(flags)
    monkeypatch.setattr(Channel, "commit_validated", no_commit)
    r = drive()
    assert r["correct"] is False
    assert r["compared"]["blocks_missing"]["value"] > 0
    assert r["compared"]["state_mismatches"]["value"] > 0


def test_a_block_served_by_a_fallback_rung_counts_as_failed(monkeypatch):
    start = standin.StandInProvider.verify_prepared_start

    def fell_back(self, *a, **kw):
        self.stats["sw_fallbacks"] += 1
        return start(self, *a, **kw)
    monkeypatch.setattr(standin.StandInProvider, "verify_prepared_start",
                        fell_back)
    r = drive()
    assert r["correct"] is True          # verdicts are bit-identical
    assert r["failed"] == r["attempted"]  # and the chip did not serve them


def test_a_traced_rehearsal_reports_the_span_metrics():
    r = drive(trace=1)
    assert r["correct"] is True
    assert {"validate_host_ms_per_ktx.catchup",
            "verify_call_ms_per_klane.catchup",
            "ledger_commit_ms_per_ktx.catchup"} <= set(r["metrics"])
    # no device plane on a CPU: the trace readers find nothing to read
    assert "lane_occupancy.catchup" not in r["metrics"]
    assert "device_idle_share.catchup" not in r["metrics"]
    assert "comb_digest_roofline.catchup" not in r["metrics"]
