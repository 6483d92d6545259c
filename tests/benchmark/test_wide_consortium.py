"""The cell `wide-consortium.catchup` (PR 34): its files say what
their source says (Thakkar et al.'s 4 organisations; ISSUE 34's 24 had
no source and went in review), the harness plans 4 organisations and 5
distinct keys a block from them, and a rehearsal of the cell as the
files give it (the stand-in provider in the device's place,
500-transaction blocks, a two-second window) reads `correct` true — and
false with the controls in the program's place. No file of the
benchmark is edited for it: the cell is a configuration, a manifest
entry, its name in the lists of the metrics that are there, and two
metric files.
"""

import argparse
import json
import os

import pytest

from benchmark import run, synth
from fabric_tpu import native

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "wide-consortium.catchup"

needs_native = pytest.mark.skipif(
    not native.available(),
    reason="the native block-prep library cannot be built here")


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


def test_configuration_states_the_consortium(cell):
    manifest, entry, config, traffic = cell
    default = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "default-cut-2of3.json")))
    assert entry["traffic"] == "catchup-default-cut" and entry["chips"] == 1
    assert config["channel"] == {
        "orgs": 4, "endorsing_peers_per_org": 1, "client_identities": 1,
        "distinct_p256_keys_per_block": 5}
    assert config["chaincode"]["endorsement_policy"] == (
        "OutOf(2, 'Org1MSP.member', 'Org2MSP.member', 'Org3MSP.member', "
        "'Org4MSP.member')")
    assert config["chaincode"]["endorsements_needed"] == 2
    assert config["bccsp"] == {"Default": "TPU"}
    # everything the width does not touch is the default cell's
    for group in ("orderer", "peer", "ledger", "ledger_preload", "chips"):
        assert config[group] == default[group], group
    assert "2 distinct organisations of the 4" in config["guarantees"][1]
    assert config["guarantees"][0::2] == default["guarantees"][0::2]
    # the width is the source's; what the harness cannot make of it is
    # a cut that the file lists, with the deployment it stands for
    assert len(config["source"]) <= 200 and "1805.11390" in config["source"]
    assert config["reduced"] == ["ledger_preload", "endorsing_peers_per_org"]
    cut = config["endorsing_peers_per_org"]
    assert (cut["source"], cut["here"]) == (
        2, config["channel"]["endorsing_peers_per_org"])
    assert cut["deployment"] and cut["why_cut"]


@pytest.mark.parametrize("name, keys", [("default-cut.catchup", 4),
                                        (CELL, 5)])
def test_the_provider_options_the_configuration_states_are_the_factorys(
        name, keys):
    """`bccsp_assumed` is read by nobody: hold it to the program."""
    from fabric_tpu.bccsp import factory
    config = run.load_cell(name)[2]
    assert config["channel"]["distinct_p256_keys_per_block"] == keys
    assert f"this channel's {keys} keys fill {keys}" in \
        config["bccsp_assumed"]["note"]
    assert "2,048 lanes" in config["bccsp_assumed"]["note"]
    opts = factory.FactoryOpts.from_config(config["bccsp"])
    assumed = config["bccsp_assumed"]
    assert opts.tpu.max_keys == assumed["MaxKeys"]
    assert opts.tpu.table_cache_bytes == assumed["TableCacheMB"] << 20
    assert opts.tpu.min_batch == assumed["MinBatch"]


def test_every_block_carries_5_distinct_keys(cell):
    _, _, config, traffic = cell
    orgs = config["channel"]["orgs"]
    block_txs = config["orderer"]["BatchSize"]["MaxMessageCount"]
    warm = synth.preload_blocks(traffic["transactions"], block_txs) \
        + traffic["warmup_blocks"]
    plans = synth.plan_chain(2 ** 31 + 34, warm + 6, block_txs,
                             traffic["transactions"], orgs)
    for plan in plans:
        endorsers = {o for tx in plan.txs for o in tx.endorsers}
        # 4 endorsing organisations and the one client
        assert len(endorsers) + 1 == 5 \
            == config["channel"]["distinct_p256_keys_per_block"]
        assert all(len(set(tx.endorsers)) == 2 for tx in plan.txs)
    assert sum(1 + len(tx.endorsers) for tx in plans[-1].txs) == 1500


def test_the_cell_reports_what_the_manifest_lists(cell):
    """Every per-layer metric of the default cell is the new cell's
    too, under the same name (one entry, two cells in its list), and
    the two that read what PR 34 adds are its own."""
    manifest = cell[0]
    listed = {m["name"]: m for m in manifest["per_layer"]
              if CELL in m["workloads"]}
    shared = {n for n, m in listed.items()
              if m["workloads"] == ["default-cut.catchup", CELL]}
    assert len(shared) == 18 and all(n.endswith(".catchup") for n in shared)
    assert set(listed) - shared == {"key_tables_ms_per_klane.wide",
                                    "key_slot_hit_share.wide"}
    assert all(m["moves"] == "commit_tx_per_s" for m in listed.values())
    rate = [m for m in manifest["end_to_end"]
            if m["name"] == "commit_tx_per_s"][0]
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.07
    mdir = os.path.join(ROOT, "benchmark", "metrics")
    # a file a manifest entry, and none left over
    assert sorted(os.listdir(mdir)) == sorted(
        m["name"] + ".json" for m in manifest["per_layer"])
    assert json.load(open(os.path.join(
        mdir, "key_tables_ms_per_klane.wide.json"))) == {
        "reader": "program.span_ms_per_klane",
        "params": {"spans": ["tpu.tables"]}}
    assert json.load(open(os.path.join(
        mdir, "key_slot_hit_share.wide.json"))) == {
        "reader": "program.counter_ratio",
        "params": {"num": "key_slot_hits", "den": "key_slot_lookups"}}


def _rehearse(cell, control=""):
    manifest, entry, config, traffic = cell
    args = argparse.Namespace(
        workload=CELL, seed=2 ** 31 + 34, seconds=2.0, trace=0,
        control=control, rehearse=True, workers=2)
    rc, result = run.execute(manifest, entry, config, traffic, args)
    assert rc == 4, "a rehearsal is never a pass"
    return result


@needs_native
def test_a_rehearsal_of_the_cell_is_correct(cell):
    r = _rehearse(cell)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= 2
    assert all(v["value"] == 0 for v in r["compared"].values())
    assert set(r["metrics"]) == {"commit_tx_per_s", "setup_s"}
    assert r["info"]["signatures_reverified"] >= 1500
    # 4,000 tx/s of the shipped traffic file over the 2 s window
    assert r["info"]["supply_blocks"] == 17
    assert r["info"]["window_blocks"] == r["attempted"]
    assert 0 < r["info"]["supply_used_share"] <= 1


@needs_native
def test_the_controls_read_not_correct_in_the_cell(cell):
    r = _rehearse(cell, control="accept_high_s,skip_mvcc")
    assert r["correct"] is False
    for c in run.CONTROLS:
        assert r["compared"][f"control.{c}.flag_mismatches"]["value"] > 0
    # the program itself, in the same run, still reads sound
    assert r["compared"]["flag_mismatches"]["value"] == 0
