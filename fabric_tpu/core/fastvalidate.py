"""Block validation fast path: native host pipeline + array dispatch.

Round-3 verdict: the device kernel crossed the 10x line but ~90% of
its advantage died in per-tx Python between the wire and the device.
This module replaces phase 1's per-tx protobuf unmarshals and the
provider's per-item staging loop with ONE native pass over the block
(native/blockprep.cpp: wire-format field extraction, SHA-256 digest
lanes — SHA-NI when the host has it — rwset write scanning, identity
dedup, DER signature staging) followed by ONE array dispatch
(`TPUProvider.verify_prepared_start`). The dispatch happens BEFORE the
Python policy phase so device execution overlaps host policy work.
Policy matching is memoized block-wide: principal matching evaluates
once per distinct (policy, valid-identity-sequence), key metadata and
duplicate-txid probes are batched per block, and "plain" transactions
(simple public writes, no key-level parameters in play) shortcut to a
single memo lookup.

SEMANTICS: byte-identical to `TxValidator._validate_reference_path`
(the oracle). The native parser decides only cleanly-encoded
transactions; anything unusual (unknown fields, non-minimal
encodings, >MAX_E endorsements, custom validation plugins, unclean
rwsets) routes that tx through the reference per-tx path inside the
same block (`_phase1_tx`). Differential tests:
tests/test_fastvalidate.py.

Reference analog: `core/committer/txvalidator/v20/validator.go:180-265`
(Validate) — the goroutine fan-out becomes the native parallel parse,
the per-tx VSCC becomes the batched array dispatch.
"""

from __future__ import annotations

import logging
import os

import numpy as np

from fabric_tpu import native
from fabric_tpu.common import tracing
from fabric_tpu.common.policies import policy as papi
from fabric_tpu.core import statebased
from fabric_tpu.core.policycheck import (
    ApplicationPolicyEvaluator, org_member_policy_bytes,
)
from fabric_tpu.ledger import pvtdata as pvt
from fabric_tpu.protos import rwset as rwpb, transaction as txpb

logger = logging.getLogger("txvalidator.fast")

TVC = txpb.TxValidationCode
MAX_E = 8                       # endorsements per tx in the flat tables
_INVALID_ENDORSER = native.BP_FAIL_BASE + TVC.INVALID_ENDORSER_TRANSACTION


def available(csp) -> bool:
    """The fast path needs the native library and a provider with the
    prepared-array entry (the TPU provider). FTPU_FAST_VALIDATE=0
    forces the reference path (debugging/differential runs)."""
    return (os.environ.get("FTPU_FAST_VALIDATE", "1") != "0"
            and hasattr(csp, "verify_prepared_start")
            and native.available())


def _parse_write_info(cc_name: str, results: bytes):
    """rwset walk for the VSCC (same parsers as the reference path)."""
    def kv_parser(raw):
        kv = rwpb.KVRWSet()
        kv.ParseFromString(raw)
        return kv

    def hashed_parser(raw):
        h = rwpb.HashedRWSet()
        h.ParseFromString(raw)
        return h

    txrw = rwpb.TxReadWriteSet()
    txrw.ParseFromString(results)
    return statebased.extract_write_info(cc_name, txrw, kv_parser,
                                         hashed_parser)


def validate_fast(v, block, bundle):
    """One-shot fast validation. `v` is the TxValidator. Returns
    (codes, n_signature_lanes) or None when the block cannot take the
    fast path at all."""
    from fabric_tpu.core import handlers
    from fabric_tpu.core.txvalidator import _TxCheck

    envs = list(block.data.data)
    n = len(envs)
    # one span per phase, whatever the block holds: host prep up to the
    # provider call, the policy phase the device runs under, and
    # from the resolver to the codes
    prep = tracing.span("validate.prep", txs=n)
    with prep:
        bp = native.block_prep(envs, v._channel_id, MAX_E)
        if bp is None:
            return None

        codes: list[int] = [TVC.NOT_VALIDATED] * n
        status = bp.status

        # ---- unique identities: deserialize + validate ONCE each ----
        deser = bundle.msp_manager
        idents: list = [None] * bp.n_unique      # None = undeserializable
        creator_ok = np.zeros(bp.n_unique + 1, dtype=bool)
        ident_live = np.zeros(bp.n_unique + 1, dtype=bool)
        for uid in range(bp.n_unique):
            raw = bp.unique_identity(uid)
            try:
                ident = deser.deserialize_identity(raw)
            except Exception as e:
                logger.debug("invalid identity skipped: %s", e)
                continue
            idents[uid] = ident
            ident_live[uid] = True
            try:
                ident.validate()
                creator_ok[uid] = True
            except Exception as e:
                logger.debug("identity fails validation: %s", e)

        # ---- optimistic lane assembly + EARLY async dispatch ----
        # every structurally-OK tx contributes lanes now, before
        # creator/dup/policy triage: wasted lanes are rare and harmless,
        # and dispatching first lets the device run under the whole
        # Python policy phase.
        ok_mask = (status == native.BP_OK_ENDORSER) | \
                  (status == native.BP_OK_CONFIG)
        ci = np.nonzero(ok_mask)[0]
        nc = len(ci)
        creator_pos = np.full(n, -1, dtype=np.int64)
        creator_pos[ci] = np.arange(nc)

        e_uid = bp.e_uid
        slot = np.arange(MAX_E)[None, :]
        lane_mask = ok_mask[:, None] & (slot < bp.e_count[:, None])
        # within-tx dedup: keep the FIRST slot of each identity
        for j in range(1, MAX_E):
            dup = np.zeros(n, dtype=bool)
            for k in range(j):
                dup |= e_uid[:, j] == e_uid[:, k]
            lane_mask[:, j] &= ~dup
        # drop undeserializable endorser identities (prepare_signature_set
        # skip semantics)
        lane_mask &= ident_live[np.clip(e_uid, 0, bp.n_unique)] & \
            (e_uid >= 0)
        ei, ej = np.nonzero(lane_mask)
        ne = len(ei)

        def cat(a_c, a_e):
            if nc and ne:
                return np.concatenate([a_c, a_e])
            return a_c if nc else a_e

        # an identity without a bccsp `.key` (e.g. idemix pseudonyms, whose
        # verify key is internal to verify_item) cannot be staged as array
        # lanes, and neither can message-based schemes (Ed25519 modern-MSP
        # identities: the staged lanes carry pre-hashed digests, but the
        # scheme signs the full message); txs touching either reroute
        # per-tx through the reference path
        keys = [getattr(ident, "key", None) for ident in idents]
        unstageable = np.array(
            [ident is not None and
             (key is None or getattr(key, "sign_message", False))
             for ident, key in zip(idents, keys)] + [False])
        tx_unstageable = unstageable[np.clip(bp.creator_uid, 0,
                                             bp.n_unique)]
        e_unstageable = unstageable[np.clip(e_uid, 0, bp.n_unique)] & \
            (e_uid >= 0) & (slot < bp.e_count[:, None])
        tx_unstageable = tx_unstageable | e_unstageable.any(axis=1)

        if nc + ne:
            digests = cat(bp.payload_digest[ci], bp.e_digest[ei, ej])
            r = cat(bp.c_r[ci], bp.e_r[ei, ej])
            rpn = cat(bp.c_rpn[ci], bp.e_rpn[ei, ej])
            w = cat(bp.c_w[ci], bp.e_w[ei, ej])
            der_ok = cat(bp.c_ok[ci], bp.e_ok[ei, ej])
            key_idx = cat(bp.creator_uid[ci].astype(np.int32),
                          e_uid[ei, ej].astype(np.int32))

            def get_sig(lane: int) -> bytes:
                if lane < nc:
                    return bp.slice(int(ci[lane]), bp.csig_off,
                                    bp.csig_len)
                k = lane - nc
                i, j = int(ei[k]), int(ej[k])
                o = int(bp.e_sig_off[i, j])
                return envs[i][o:o + int(bp.e_sig_len[i, j])]
        prep.set(identities=bp.n_unique, lanes=nc + ne)

    if nc + ne:
        resolve = v._csp.verify_prepared_start(
            digests, r, rpn, w, der_ok, key_idx, keys, get_sig)
    else:
        resolve = lambda: []  # noqa: E731

    policy = tracing.span("validate.policy", txs=n)
    with policy:
        # ---- block-scope caches ----
        evaluator = ApplicationPolicyEvaluator(
            bundle.policy_manager, bundle.msp_manager, v._csp)
        eval_cache: dict = {}
        vp_cache: dict = {}
        org_pols: dict = {}
        cc_info: dict = {}     # cc_name -> (policy|None, is_default, error)

        def cc_policy_of(cc_name: str):
            hit = cc_info.get(cc_name)
            if hit is None:
                definition = v._cc_definition(cc_name)
                plugin = (definition.validation_plugin
                          if definition is not None and
                          getattr(definition, "validation_plugin", None)
                          else handlers.DEFAULT_VALIDATION)
                pol, err = None, None
                try:
                    if definition is not None and \
                            definition.endorsement_policy:
                        pol = evaluator.resolve(
                            definition.endorsement_policy)
                    else:
                        pol = bundle.policy_manager.get_policy(
                            "/Channel/Application/Endorsement")
                except Exception as e:
                    err = e
                hit = (pol, plugin == handlers.DEFAULT_VALIDATION, err)
                cc_info[cc_name] = hit
            return hit

        def org_policies_of(orgs):
            out = []
            for org in orgs:
                pol = org_pols.get(org)
                if pol is None:
                    pol = evaluator.resolve(org_member_policy_bytes(org))
                    org_pols[org] = pol
                out.append(pol)
            return out

        # block-scope key-metadata view, batch-filled before phase 3
        md_view: dict = {}
        md_wanted: list = []

        def md_getter_for(cc_name: str):
            def getter(coll, key):
                ns = cc_name if coll is None else pvt.hash_ns(cc_name, coll)
                return md_view.get((ns, key))
            return getter

        # ---- batched duplicate-txid probe ----
        endorser_mask = (status == native.BP_OK_ENDORSER) | \
                        (status == _INVALID_ENDORSER)
        candidate_ids = [bp.tx_id(i) for i in np.nonzero(endorser_mask)[0]]
        if candidate_ids and hasattr(v._ledger, "existing_tx_ids"):
            committed = v._ledger.existing_tx_ids(candidate_ids)
        else:
            committed = {t for t in candidate_ids
                         if v._ledger.get_transaction_by_id(t) is not None}

        # ---- phase 1 (ordered, light) ----
        # pending entries, in block order:
        #   ("plain", i, cc_name, keys)        — memoized verdict in phase 3
        #   ("rich", i, cc_name, klp)          — KeyLevelPrepared finish
        #   ("config", i, check)               — config replay
        #   ("py", check)                      — reference-path tx
        # seeded with the commit pipeline's validated-but-uncommitted
        # predecessor tx-ids (empty on the sequential path)
        txids_in_block: set = set(v._known_txids)
        pending: list = []
        py_checks: list[_TxCheck] = []

        def reroute(i):
            code, check = v._phase1_tx(i, envs[i], bundle, txids_in_block)
            if code != TVC.NOT_VALIDATED:
                codes[i] = code
            else:
                py_checks.append(check)
                pending.append(("py", check))

        def make_rich(i, cc_name, write_info):
            """KeyLevelPrepared over pre-deduped lanes (the reference
            builtin_vscc_prepare, minus the re-deserialization)."""
            cc_pol, _, cc_err = cc_policy_of(cc_name)
            if cc_err is not None:
                raise cc_err
            orgs = org_policies_of(write_info.implicit_orgs)
            lane_idents = [idents[int(u)]
                           for u in e_uid[i][lane_mask[i]]]
            prepared = papi.PreparedSignatureSet(lane_idents, [])
            for coll, key in write_info.written_keys:
                ns = cc_name if coll is None else pvt.hash_ns(cc_name, coll)
                md_wanted.append((ns, key))
            return statebased.KeyLevelPrepared(
                cc_policy=cc_pol, org_policies=orgs, info=write_info,
                overlay=v._overlay, cc_name=cc_name,
                metadata_getter=md_getter_for(cc_name),
                evaluator=evaluator, deserializer=deser, csp=v._csp,
                prepared=prepared, eval_cache=eval_cache,
                vp_cache=vp_cache)

        rw_mode = bp.rw_mode
        for i in range(n):
            st = status[i]
            if st == native.BP_NEEDS_PYTHON:
                reroute(i)
                continue
            if st >= native.BP_FAIL_BASE and st != _INVALID_ENDORSER:
                codes[i] = int(st) - native.BP_FAIL_BASE
                continue
            if tx_unstageable[i]:
                # non-array-stageable identity (idemix): reference path
                reroute(i)
                continue
            # creator identity precedes everything else in the reference
            # order (including the duplicate-txid claim)
            if not creator_ok[int(bp.creator_uid[i])]:
                logger.debug("tx[%d] creator invalid", i)
                codes[i] = TVC.BAD_CREATOR_SIGNATURE
                continue
            if st == native.BP_OK_CONFIG:
                pending.append(("config", i, _TxCheck(
                    index=i, creator_item=None,
                    config_envelope=bp.slice(i, bp.config_off,
                                             bp.config_len))))
                continue
            cc_name = ""
            if st == native.BP_OK_ENDORSER:
                cc_name = bp.slice(i, bp.ccname_off,
                                   bp.ccname_len).decode()
                _, is_default, _ = cc_policy_of(cc_name)
                if not is_default:
                    # custom validation plugin: reference path for this tx
                    reroute(i)
                    continue
            tx_id = bp.tx_id(i)
            if tx_id in txids_in_block or tx_id in committed:
                codes[i] = TVC.DUPLICATE_TXID
                continue
            txids_in_block.add(tx_id)
            if st == _INVALID_ENDORSER:
                codes[i] = TVC.INVALID_ENDORSER_TRANSACTION
                continue
            if rw_mode[i] == native.RW_PLAIN:
                # chaincode resolvability is a phase-1 decision in the
                # reference (prepare stage) — it precedes the crypto
                # results, so a bad-signature tx on an unresolvable
                # chaincode still reads INVALID_CHAINCODE
                _, _, cc_err = cc_policy_of(cc_name)
                if cc_err is not None:
                    logger.debug("tx[%d] chaincode %s unresolvable: %s",
                                 i, cc_name, cc_err)
                    codes[i] = TVC.INVALID_CHAINCODE
                    continue
                nk = int(bp.rw_nkeys[i])
                wkeys = []
                for k in range(nk):
                    o = int(bp.rw_key_off[i, k])
                    key = envs[i][o:o + int(bp.rw_key_len[i, k])].decode()
                    wkeys.append(key)
                    md_wanted.append((cc_name, key))
                pending.append(("plain", i, cc_name, wkeys))
                continue
            # rich / unparsed: reference rwset walk for this tx
            try:
                write_info = _parse_write_info(
                    cc_name, bp.slice(i, bp.results_off, bp.results_len))
            except Exception as e:
                logger.debug("tx[%d] bad endorsed action: %s", i, e)
                codes[i] = TVC.INVALID_ENDORSER_TRANSACTION
                continue
            try:
                klp = make_rich(i, cc_name, write_info)
            except Exception as e:
                logger.debug("tx[%d] chaincode %s unresolvable: %s",
                             i, cc_name, e)
                codes[i] = TVC.INVALID_CHAINCODE
                continue
            pending.append(("rich", i, cc_name, klp))

        # ---- batched key-metadata prefetch ----
        state_db = getattr(v._ledger, "state_db", None)
        if md_wanted and state_db is not None:
            if hasattr(state_db, "get_state_metadata_many"):
                md_view.update(state_db.get_state_metadata_many(md_wanted))
            else:
                for ns, key in md_wanted:
                    md_view[(ns, key)] = state_db.get_state_metadata(
                        ns, key)
        policy.set(evals=len(pending))

    with tracing.span("validate.flags", txs=n):
        # ---- phase 2: resolve the early dispatch ----
        flags = resolve()
        e_flag = np.zeros((n, MAX_E), dtype=bool)
        if ne:
            e_flag[ei, ej] = np.asarray(flags[nc:], dtype=bool)

        py_items = []
        for c in py_checks:
            py_items.append(c.creator_item)
            if c.prepared_policy is not None:
                py_items.extend(c.prepared_policy.items)
        py_ok = v._csp.verify_batch(py_items) if py_items else []

        # ---- phase 3 (ordered) ----
        def plain_eval(pol, identities) -> int:
            """Memoized cc-policy evaluation (shared cache + semantics
            with KeyLevelPrepared._eval). Equivalent to
            KeyLevelPrepared.finish for a tx whose every written key
            resolves to no validation parameter."""
            if pol is None:
                return TVC.VALID
            try:
                statebased.memoized_evaluate(eval_cache, pol, identities)
                return TVC.VALID
            except papi.PolicyError:
                return TVC.ENDORSEMENT_POLICY_FAILURE
            except Exception as e:
                logger.warning("policy evaluation error: %s", e)
                return TVC.INVALID_OTHER_REASON

        py_pos = 0
        overlay = v._overlay
        for entry in pending:
            kind = entry[0]
            if kind == "py":
                c = entry[1]
                cflag = py_ok[py_pos]
                py_pos += 1
                nit = len(c.prepared_policy.items) \
                    if c.prepared_policy is not None else 0
                eflags = py_ok[py_pos:py_pos + nit]
                py_pos += nit
                codes[c.index] = v.finish_check(c, cflag, eflags)
                continue
            i = entry[1]
            cflag = bool(flags[creator_pos[i]])
            if kind == "config":
                codes[i] = v.finish_check(entry[2], cflag, [])
                continue
            if not cflag:
                codes[i] = TVC.BAD_CREATOR_SIGNATURE
                continue
            cc_name = entry[2]
            if kind == "plain":
                wkeys = entry[3]
                # a plain tx escalates when any of its keys has committed
                # metadata or an in-block validation-parameter update
                escalate = any(
                    md_view.get((cc_name, k)) is not None or
                    (overlay._vp and
                     overlay.get(cc_name, None, k) is not None)
                    for k in wkeys)
                if escalate:
                    try:
                        write_info = _parse_write_info(
                            cc_name, bp.slice(i, bp.results_off,
                                              bp.results_len))
                        klp = make_rich(i, cc_name, write_info)
                    except Exception as e:
                        logger.debug("tx[%d] escalation failed: %s", i, e)
                        codes[i] = TVC.INVALID_CHAINCODE
                        continue
                    kind = "rich"
                    entry = (kind, i, cc_name, klp)
                else:
                    cc_pol, _, _ = cc_policy_of(cc_name)
                    valid = [idents[int(u)]
                             for u, f in zip(e_uid[i][lane_mask[i]],
                                             e_flag[i][lane_mask[i]])
                             if f]
                    codes[i] = plain_eval(cc_pol, valid)
                    continue
            # rich: full key-level finish over this tx's lanes
            klp = entry[3]
            eflags = [bool(f) for f in e_flag[i][lane_mask[i]]]
            check = _TxCheck(index=i, creator_item=None,
                             prepared_policy=klp)
            codes[i] = v.finish_check(check, True, eflags)

    return codes, nc + ne + len(py_items)
