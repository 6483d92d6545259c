"""History database: key → chronological list of writing transactions.

Rebuild of `core/ledger/kvledger/history/{db.go,query_executer.go}`:
index entries (ns, key, block, tx) added for every write of every VALID
tx at commit; `get_history_for_key` walks them newest-first and pulls
values out of the block store (the history DB stores no values).
"""

from __future__ import annotations

import struct
from typing import Iterator, Optional

from fabric_tpu import protoutil as pu
from fabric_tpu.ledger.blkstorage import BlockStore
from fabric_tpu.ledger.kvdb import DBHandle
from fabric_tpu.ledger.txmgr import (
    BlockRWSets,
    extract_tx_rwset,
    parse_block_rwsets,
)
from fabric_tpu.protos import common, proposal as proppb
from fabric_tpu.protos import rwset as rwpb, transaction as txpb

_SEP = b"\x00"


class HistoryDB:
    def __init__(self, db: DBHandle):
        self._db = db

    @staticmethod
    def _k(ns: str, key: str, block: int, tx: int) -> bytes:
        return (ns.encode() + _SEP + key.encode() + _SEP +
                struct.pack(">QQ", block, tx))

    def commit_block(self, block: common.Block, codes: list[int],
                     parsed: Optional[BlockRWSets] = None) -> int:
        """One row per key a valid tx wrote; returns the rows written.
        `parsed` is the commit path's decoded rwsets; a caller that
        holds only the block has the envelopes decoded here."""
        if parsed is None:
            parsed = parse_block_rwsets(
                [extract_tx_rwset(e) for e in block.data.data], codes)
        batch = self._db.new_batch()
        for tx_num, tx in enumerate(parsed.txs):
            if tx is None or \
                    codes[tx_num] != txpb.TxValidationCode.VALID:
                continue
            for ns, kv, _colls in tx:
                for w in kv.writes:
                    batch.put(self._k(ns, w.key, block.header.number,
                                      tx_num), b"")
        self._db.write_batch(batch)
        return len(batch.ops)

    def get_history_for_key(self, block_store: BlockStore, ns: str,
                            key: str) -> Iterator[dict]:
        """Newest-first {tx_id, value, is_delete, block, tx} entries
        (reference: query_executer.go GetHistoryForKey)."""
        prefix = ns.encode() + _SEP + key.encode() + _SEP
        entries = [k for k, _ in self._db.iterate(prefix,
                                                  prefix + b"\xff" * 16)]
        for k in reversed(entries):
            block_num, tx_num = struct.unpack(">QQ", k[len(prefix):])
            block = block_store.get_block_by_number(block_num)
            env_bytes = block.data.data[tx_num]
            env = pu.unmarshal_envelope(env_bytes)
            ch = pu.get_channel_header(pu.get_payload(env))
            action = pu.get_action_from_envelope(env_bytes)
            txrw = rwpb.TxReadWriteSet()
            txrw.ParseFromString(action.results)
            for nsrw in txrw.ns_rwset:
                if nsrw.namespace != ns:
                    continue
                kv = rwpb.KVRWSet()
                kv.ParseFromString(nsrw.rwset)
                for w in kv.writes:
                    if w.key == key:
                        yield {
                            "tx_id": ch.tx_id,
                            "value": bytes(w.value),
                            "is_delete": w.is_delete,
                            "block": block_num,
                            "tx": tx_num,
                        }
