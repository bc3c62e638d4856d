"""Ed25519 signs messages, not digests: a 3-node cluster signs waves of
raw Solana transaction messages (150 to 1,167 bytes, no two of a wave
alike in length) through ``client.sign_transaction``. Tier-1, CPU, waves
of 8 over 16 wallets.

The reference: RFC 8032 verification by OpenSSL over the RAW message under
keys OpenSSL made (``benchmark/reference.py``, ``benchmark/wallets.py``: the
program sees only Shamir shares). What the test holds besides: the
challenge never went to the host, a second wave of other lengths asked
for no compile (lengths are data to the hash program), and the bytes that
passed were counted where the issue says.
"""
import os
import random
import threading
import time

import pytest

from benchmark import harness
from benchmark.served import CompileCounter
from mpcium_tpu import wire
from mpcium_tpu.cluster import LocalCluster, load_test_preparams
from mpcium_tpu.ops import hash_suite
from mpcium_tpu.perf import compile_watch
from mpcium_tpu.trace import recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SCHEME = harness.load_scheme(BENCH, "ed25519")
LAYOUT = harness._load_module(
    os.path.join(BENCH, "traffic", "solana_layout.py"))
WAVE, WALLETS, THRESHOLD = 8, 16, 1
SEED = 3_000_000_043
MIX = {"shares": {"transfer": 0.6, "transfer_checked": 0.3,
                  "program_call": 0.1},
       "program_call_bytes": [400, 1167], "program_call_accounts": [8, 24]}


@pytest.fixture()
def cluster(tmp_path):
    compile_watch.reset()
    c = LocalCluster(
        n_nodes=3, threshold=THRESHOLD, root_dir=str(tmp_path),
        preparams=load_test_preparams(), batch_signing=True,
        batch_window_s=60.0, reply_timeout_s=120.0, batch_max_batch=WAVE,
        loopback_workers=WAVE + 64)
    yield c
    c.close()
    compile_watch.reset()


def _messages(rng, pubkeys, wallets):
    """A wave: the first message a program call at the longest length
    (the wave's rung), then a transfer, a token transfer, and program
    calls of drawn lengths: no two alike."""
    out = []
    for i, w in enumerate(wallets):
        blockhash = rng.randbytes(32)
        r = random.Random(blockhash)
        if i == 0:
            out.append(LAYOUT.draw(pubkeys[w], blockhash, MIX, at_cap=True))
        elif i == 1:
            out.append(LAYOUT.transfer(pubkeys[w], blockhash, r))
        elif i == 2:
            out.append(LAYOUT.transfer_checked(pubkeys[w], blockhash, r))
        else:
            out.append(LAYOUT.program_call(
                pubkeys[w], blockhash, r, rng.randrange(400, 1167), 12))
    return out


def _wave(cluster, index, wallets, messages, timeout_s=300.0):
    events, done = {}, threading.Event()

    def on_result(ev):
        events[ev.tx_id] = ev
        if len(events) == len(wallets):
            done.set()

    sub = cluster.client.on_sign_result(on_result)
    try:
        for i, (w, m) in enumerate(zip(wallets, messages)):
            cluster.client.sign_transaction(wire.SignTxMessage(
                key_type="ed25519", wallet_id=f"raw-{w}",
                network_internal_code="sol", tx_id=f"raw-{index}-{i}",
                tx=m, priority=wire.PRIORITY_BULK))
        assert done.wait(timeout_s), f"{len(events)}/{len(wallets)} done"
    finally:
        sub.unsubscribe()
    return [events[f"raw-{index}-{i}"] for i in range(len(wallets))]


def _total(cluster, name):
    return sum(s["counters"].get(name, 0.0)
               for s in cluster.metrics_snapshot().values())


def test_a_cluster_signs_ragged_raw_messages_and_lengths_never_compile(
        cluster):
    rng = random.Random(SEED)
    pubkeys, records = SCHEME.make_wallets(
        WALLETS, cluster.node_ids, THRESHOLD, rng, {})
    for nid, node in cluster.nodes.items():
        for w, record in enumerate(records[nid]):
            node.save_share(record, f"raw-{w}")
    counter = CompileCounter()
    recorder.snapshot_all(clear=True)
    sent = []
    compiles, programs = [], []
    for index in range(2):
        wallets = rng.sample(range(WALLETS), WAVE)
        messages = _messages(rng, pubkeys, wallets)
        assert len({len(m) for m in messages}) == WAVE
        assert (len(messages[0]), len(messages[1]), len(messages[2])) == (
            1167, 150, 214)
        before = counter.snapshot()["requests"]
        results = _wave(cluster, index, wallets, messages)
        compiles.append(counter.snapshot()["requests"] - before)
        programs.append(hash_suite.sha512_masked._cache_size())
        for w, m, ev in zip(wallets, messages, results):
            assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason
            # OpenSSL, over the raw message, under the key OpenSSL made
            assert SCHEME.verifies(pubkeys[w], m, bytes.fromhex(ev.signature))
        sent.append(messages)
    # the second wave's lengths are other lengths, and asked for nothing
    assert [len(m) for m in sent[0]] != [len(m) for m in sent[1]]
    # (the first may have: a worker that ran this shape before has it)
    assert compiles[1] == 0
    assert programs[0] == programs[1] >= 1  # one hash program, both waves
    assert _total(cluster, "party.eddsa.host_hash_rows_total") == 0
    # every party of both waves hashed every lane at the 16-block rung
    assert _total(cluster, "party.eddsa.hash_blocks_total") == 2 * 3 * WAVE * 16
    assert _total(cluster, "scheduler.fallback_total") == 0
    sent_bytes = sum(len(m) for wave in sent for m in wave)
    assert _total(cluster, "intake.tx_bytes_total") == 3 * sent_bytes
    deadline = time.monotonic() + 10.0
    while True:  # a party's last span closes after the client's last result
        spans = [s for found, _dropped in recorder.snapshot_all().values()
                 for s in found]
        hashed = [s for s in spans
                  if s["name"] == "phase:bsign_aggregate_partial"]
        if len(hashed) >= 6 or time.monotonic() >= deadline:
            break
        time.sleep(0.02)
    assert len(hashed) == 6  # three parties, two waves, one cohort
    assert sorted(s["attrs"]["msg_bytes"] for s in hashed) == sorted(
        3 * [sum(len(m) for m in wave) for wave in sent])
    assert {s["attrs"]["hash_blocks"] for s in hashed} == {WAVE * 16}
    admitted = [s for s in spans if s["name"] == "host:manifest_admit"]
    assert len(admitted) == 6
    for s in admitted:  # hex payload and signature of every request ride in it
        assert s["attrs"]["bytes"] > 2 * min(
            sum(len(m) + 64 for m in wave) for wave in sent)
    assert _total(cluster, "batch.manifest_bytes_total") == sum(
        s["attrs"]["bytes"] for s in admitted)
    assert sorted({e["shape"] for e in compile_watch.entries()
                   if e["engine"] == SCHEME.ENGINE}) == [f"B{WAVE}|q3"]
