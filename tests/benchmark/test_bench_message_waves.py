"""What PR 43 adds to the benchmark: the traffic kind ``solana_messages``
(for a seed, the very wallets ``closed_waves`` walks, each request a
well-formed legacy Solana message that a parser of this file's own reads
back; loaded against a program whose SHA-512 cannot take rows of different
lengths, it raises at once), the configuration ``ed25519-2of3-solana``
(``ed25519-2of3`` but for the message it signs), the four readers, and a
CPU rehearsal of the cell with its control: with the challenge hash forced
to the host the generator raises and no result is printed."""
import json
import os
import struct
from collections import Counter
from types import SimpleNamespace

import pytest
from test_bench_cold_sweep import _Recording, _generator, _mix
from test_bench_rehearsal import (  # noqa: F401 — steer is a fixture
    _manifest, _RecordedTracer, _run, printed, steer)
from test_bench_stage_readers import hist, make_run, span

from benchmark import harness, hash_bytes, peaks
from mpcium_tpu.ops import hash_suite

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "ed25519-2of3-solana.message-waves"
NEW = ["wire.tx_bytes_per_sign", "batch.manifest_kb_per_wave",
       "challenge.device_ms_per_wave", "challenge.hbm_roofline_pct"]
SEEDS = [0, 43, 3_000_000_019]  # the last: more than 32 signed bits hold
NODES = ["node0", "node1", "node2"]


# -- a parser of this file's own (Solana docs, "Transactions") -------------------

def _compact_u16(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def parse(message):
    """-> the legacy message's parts; raises where it is not well formed
    or a byte is left over."""
    assert message[0] < 0x80, "a versioned message"
    signers, ro_signed, ro_unsigned = message[:3]
    n_keys, at = _compact_u16(message, 3)
    keys = [message[at + 32 * i: at + 32 * (i + 1)] for i in range(n_keys)]
    at += 32 * n_keys
    blockhash = message[at: at + 32]
    at += 32
    n_instructions, at = _compact_u16(message, at)
    instructions = []
    for _ in range(n_instructions):
        program = message[at]
        n_accounts, at = _compact_u16(message, at + 1)
        accounts = list(message[at: at + n_accounts])
        n_data, at = _compact_u16(message, at + n_accounts)
        data = message[at: at + n_data]
        assert len(data) == n_data
        at += n_data
        assert program < n_keys and all(a < n_keys for a in accounts)
        instructions.append((program, accounts, data))
    assert at == len(message), "bytes left over"
    assert all(len(k) == 32 for k in keys) and len(blockhash) == 32
    assert 1 <= signers <= n_keys and ro_signed < signers
    assert ro_unsigned <= n_keys - signers
    return SimpleNamespace(header=(signers, ro_signed, ro_unsigned),
                           keys=keys, blockhash=blockhash,
                           instructions=instructions)


# -- the generator ---------------------------------------------------------------

class _RecordingNodes(_Recording):
    """``test_bench_cold_sweep._Recording`` with the wallets' public keys
    and three nodes' intake books, which take in what a wave sends;
    ``tamper(served, index)`` lets a test move them."""

    def __init__(self, *args, tamper=None):
        super().__init__(*args)
        self.pubkeys = [struct.pack(">I", w) * 8
                        for w in range(self.n_wallets)]
        self.books = {nid: {"counters": {"intake.tx_bytes_total": 0.0},
                            "histograms": {"intake.handle_s": hist(0.0, 0)}}
                      for nid in NODES}
        self.tamper = tamper

    def run_wave(self, index, measured, wallets, messages, *args):
        for book in self.books.values():
            book["counters"]["intake.tx_bytes_total"] += sum(
                len(m) for m in messages)
            book["histograms"]["intake.handle_s"]["count"] += len(messages)
        if self.tamper is not None:
            self.tamper(self, index)
        return super().run_wave(index, measured, wallets, messages, *args)

    def metrics_snapshot(self):
        return json.loads(json.dumps(self.books))

    def counter_total(self, name):
        return sum(b["counters"].get(name, 0.0) for b in self.books.values())


def _sent(seed, n_wallets=4096, wave=64, last=70, tamper=None):
    served = _RecordingNodes(n_wallets, wave, last, tamper=tamper)
    driven = _generator("solana_messages").drive(
        served, _mix("message-waves"), seed, seconds=3600.0)
    assert len(driven["waves"]) == last + 1 == len(served.sent)
    return served


@pytest.mark.parametrize("seed", SEEDS)
def test_a_seed_fixes_the_waves_and_they_are_closed_waves_own(seed):
    mix = _mix("message-waves")
    sent = _sent(seed).sent
    assert sent == _sent(seed).sent  # a seed fixes the inputs
    assert sent != _sent(seed + 1).sent
    plain = _Recording(4096, 64, 70)
    _generator("closed_waves").drive(plain, mix, seed, seconds=3600.0)
    # cell 1's very wallets in cell 1's order, measured and timed alike
    assert [(s[0], s[1], s[2], s[4], s[5]) for s in sent] == [
        (s[0], s[1], s[2], s[4], s[5]) for s in plain.sent]
    assert [s[1] for s in sent[:3]] == [False, True, True]
    for ours, theirs in zip(sent, plain.sent):
        for wallet, message, draw in zip(ours[2], ours[3], theirs[3]):
            parsed = parse(message)
            assert parsed.blockhash == draw  # the 32-byte draw
            assert parsed.keys[0] == struct.pack(">I", wallet) * 8
            assert parsed.header[0] == 1 and message[0] == 1  # legacy


@pytest.mark.parametrize("seed", SEEDS)
def test_the_shares_and_sizes_are_the_mixs(seed):
    mix = _mix("message-waves")["messages"]
    sent = _sent(seed).sent
    firsts = [s[3][0] for s in sent]
    rest = [m for s in sent for m in s[3][1:]]
    assert all(len(m) == 1167 for m in firsts)  # the rung's pin
    kinds = Counter()
    for m in rest + firsts:
        parsed = parse(m)
        n = len(parsed.keys)
        if len(parsed.instructions) == 1 and n == 3:
            kinds["transfer"] += 1
            assert len(m) == 150 == mix["transfer_bytes"]
            (program, accounts, data), = parsed.instructions
            assert parsed.keys[program] == bytes(32)  # the System program
            assert accounts == [0, 1] and len(data) == 12
            assert struct.unpack("<I", data[:4]) == (2,)  # Transfer
        elif len(parsed.instructions) == 1:
            kinds["transfer_checked"] += 1
            assert (len(m), n) == (214, 5)
            assert len(m) == mix["transfer_checked_bytes"]
            (program, accounts, data), = parsed.instructions
            assert program == 4 and sorted(accounts) == [0, 1, 2, 3]
            assert len(data) == 10 and data[0] == 12  # TransferChecked
        else:
            kinds["program_call"] += 1
            assert 400 <= len(m) <= 1167 and 8 <= n <= 24
            budget, call = parsed.instructions
            assert budget[1] == [] and len(budget[2]) == 5
            assert call[0] == n - 1 and len(call[1]) >= n - 3
    total = len(rest)
    kinds["program_call"] -= len(firsts)
    for kind, share in mix["shares"].items():
        assert abs(kinds[kind] / total - share) < 0.03, (kind, kinds)
    calls = [len(m) for m in rest if len(m) >= 400]
    assert min(calls) < 450 and max(calls) > 1120  # uniform over the range
    mean = sum(len(m) for m in rest) / total
    assert abs(mean - mix["mean_bytes"]) < 12


def test_every_length_of_a_program_call_can_be_built():
    layout = _generator("solana_layout")
    import random

    for length in range(400, 1168):
        for accounts in (8, 17, 24):
            m = layout.program_call(b"\x01" * 32, b"\x02" * 32,
                                    random.Random(length), length, accounts)
            assert len(m) == length
            assert len(parse(m).keys) <= accounts
    assert layout.MESSAGE_CAP == 1167 == 1232 - 1 - 64
    assert [layout.compact_u16(n) for n in (0, 127, 128, 16383, 16384)] == [
        b"\x00", b"\x7f", b"\x80\x01", b"\xff\x7f", b"\x80\x80\x01"]


def test_a_row_hashed_on_the_host_raises_after_the_window():
    def on_host(served, index):
        if index == 2:
            served.books["node1"]["counters"][
                "party.eddsa.host_hash_rows_total"] = 64.0

    with pytest.raises(RuntimeError, match="hashed on the host"):
        _sent(1, last=3, tamper=on_host)


def test_bytes_a_node_did_not_count_raise_after_the_window():
    def short(served, index):
        if index == 1:
            served.books["node2"]["counters"]["intake.tx_bytes_total"] -= 1

    with pytest.raises(RuntimeError, match="counted otherwise"):
        _sent(1, last=3, tamper=short)
    # a node that took nothing in (it is out) is not held to the bytes
    def out(served, index):
        book = served.books["node0"]
        book["counters"]["intake.tx_bytes_total"] = 0.0
        book["histograms"]["intake.handle_s"]["count"] = 0

    _sent(1, last=3, tamper=out)


def test_a_program_without_the_masked_hash_is_refused_when_loaded(
        monkeypatch):
    """On the parent commit the cell fails at ``harness.Cell(...)``, before
    JAX is asked for a device and before a wallet is made."""
    monkeypatch.delattr(hash_suite, "sha512_masked")
    with pytest.raises(RuntimeError, match="hash_suite.sha512_masked"):
        _generator("solana_messages")
    with pytest.raises(RuntimeError, match="rows of different lengths"):
        harness.Cell(ROOT, CELL)
    harness.Cell(ROOT, "ed25519-2of3.bulk-waves")  # the others still load
    monkeypatch.undo()
    assert harness.Cell(ROOT, CELL).traffic["kind"] == "solana_messages"


# -- the configuration and the manifest -----------------------------------------

def test_the_configuration_is_ed25519_2of3_but_for_the_message():
    with open(os.path.join(BENCH, "configs", "ed25519-2of3.json")) as fh:
        base = json.load(fh)
    config = harness.Cell(ROOT, CELL).config
    assert set(config) == set(base) | {"message"}
    assert config["scheme"] == dict(base["scheme"], digest_bytes=1167)
    for group in ("population", "layout", "serving", "reduced"):
        assert config[group] == base[group], group
    assert config["reduced"] == []
    differ = {k for k in base["guarantees"]
              if config["guarantees"][k] != base["guarantees"][k]}
    assert differ == {"signature_validity"} and set(
        config["guarantees"]) == set(base["guarantees"])
    assert "over the raw message, no prehash" in config["guarantees"][
        "signature_validity"]
    assumed = dict(config["assumed"])
    assert "no public source" in assumed.pop("message_mix")
    assert "never 0x80" in assumed.pop("legacy_messages_only")
    assert "16-block rung" in assumed.pop("digest_bytes")
    assert assumed == base["assumed"]
    message = config["message"]
    assert (message["packet_bytes"], message["longest_message_bytes"],
            message["shortest_message_bytes"]) == (1232, 1167, 150)
    assert "examples/sign" in config["source"] and len(config["source"]) <= 200
    # the warm batch's messages are as long as the traffic's longest, so
    # it compiles the rung every wave of the traffic uses
    mix = _mix("message-waves")
    assert mix["messages"]["program_call_bytes"][1] == config["scheme"][
        "digest_bytes"]
    bulk = _mix("bulk-waves")
    assert {k: v for k, v in mix.items()
            if k not in ("kind", "who", "wallet_order", "messages")} == {
        k: v for k, v in bulk.items()
        if k not in ("kind", "who", "wallet_order")}


def test_the_manifest_appends_the_cell_and_lists_it_alone_for_the_four():
    manifest = _manifest()
    config = manifest["configs"][-1]
    assert config["name"] == "ed25519-2of3-solana" and config["reduced"] == []
    assert config["file"] == "benchmark/configs/ed25519-2of3-solana.json"
    with open(os.path.join(ROOT, config["file"])) as fh:
        assert json.load(fh)["source"] == config["source"]
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": "ed25519-2of3-solana",
        "traffic": "message-waves", "chips": 1,
        "why": manifest["workloads"][-1]["why"]}
    new = manifest["per_layer"][-4:]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"],
             m["moves"]) for m in new] == [
        ("wire.tx_bytes_per_sign", "bytes", "lower", "program_counter",
         "event consumer", "sign_throughput"),
        ("batch.manifest_kb_per_wave", "kB", "lower", "program_span",
         "batch scheduler", "sign_latency_p50_ms"),
        ("challenge.device_ms_per_wave", "ms", "lower", "device_trace",
         "engine kernels", "sign_throughput"),
        ("challenge.hbm_roofline_pct", "%", "higher", "device_trace",
         "engine kernels", "sign_throughput")]
    assert all(m["workloads"] == [CELL] for m in new)
    layers = {m["layer"] for m in manifest["per_layer"][:-4]}
    assert {m["layer"] for m in new} <= layers
    # the cell reports every entry that holds everywhere, then its four
    names = [m["name"] for m in harness.Cell(ROOT, CELL).metrics("per_layer")]
    everywhere = [m["name"] for m in manifest["per_layer"]
                  if "workloads" not in m]
    assert names == everywhere + NEW


# -- the readers and the bytes ---------------------------------------------------

def _reader(name):
    return harness.Cell(ROOT, CELL).reader("per_layer", name)


def _books(tx_bytes, taken):
    counters = {} if tx_bytes is None else {
        "intake.tx_bytes_total": tx_bytes}
    return {"counters": counters, "gauges": {},
            "histograms": {"intake.handle_s": hist(0.001 * taken, taken)}}


def test_tx_bytes_per_sign_is_the_counter_over_the_requests_taken_in():
    start = {"a": _books(1000.0, 10), "b": _books(500.0, 5)}
    end = {"a": _books(1000.0 + 233.0 * 8, 18),
           "b": _books(500.0 + 233.0 * 8, 13)}
    assert _reader("wire.tx_bytes_per_sign")(
        make_run([], start, end)) == pytest.approx(233.0)
    bare = {"a": _books(None, 18)}  # the parent: no counter
    assert _reader("wire.tx_bytes_per_sign")(
        make_run([], bare, bare)) is None
    assert _reader("wire.tx_bytes_per_sign")(make_run([], {}, {})) is None


def test_manifest_kb_is_the_admit_spans_bytes_a_node_and_wave():
    spans = [span("host:manifest_admit", "a", -100, 20, bytes=9_000_000),
             span("host:manifest_admit", "a", 10, 20, bytes=700_000),
             span("host:manifest_admit", "b", 11, 20, bytes=700_000),
             span("host:manifest_admit", "a", 5000, 20, bytes=800_000),
             span("host:manifest_admit", "b", 5001, 20, bytes=800_000),
             span("host:batch_prepare", "a", 10, 700, bytes=1)]
    # 3,000,000 bytes over two nodes and two measured waves
    assert _reader("batch.manifest_kb_per_wave")(
        make_run(spans, {}, {})) == pytest.approx(750.0)
    parent = [span("host:manifest_admit", "a", 10, 20, n=8)]
    assert _reader("batch.manifest_kb_per_wave")(
        make_run(parent, {}, {})) is None
    assert _reader("batch.manifest_kb_per_wave")(
        make_run([], {}, {})) is None


def test_the_hash_bytes_are_the_rungs_rows_for_every_party():
    assert hash_bytes.RUNGS == hash_suite.SHA512_RUNGS
    for longest in (0, 32, 47, 48, 150, 214, 431, 432, 943, 944, 1167, 1967):
        assert hash_bytes.rung_cap(longest) == hash_suite.sha512_rung_cap(
            64 + longest)
    with pytest.raises(ValueError):
        hash_bytes.rung_cap(1968)
    assert hash_bytes.per_wave(1024, 3, 1167) == 3 * 1024 * (2031 + 4 + 64)
    assert hash_bytes.per_wave(1024, 3, 32) == 3 * 1024 * (111 + 4 + 64)
    # the reader finds the program under the name the trace gives it
    assert hash_bytes.PROGRAM == "jit_" + hash_suite.sha512_masked.__name__
    assert hasattr(hash_suite.sha512_masked, "lower")  # jitted as named


class _TracerWithTheHash(_RecordedTracer):
    """The made-up device plane with the masked hash among its programs:
    a hundredth of the traced wave."""

    def finish(self, run_data):
        super().finish(run_data)
        lo, hi = run_data.traced_lo_ns, run_data.traced_hi_ns
        modules = run_data.trace["planes"][1]["lines"][0]["events"]
        modules.append([f"{hash_bytes.PROGRAM}(3)", lo + (hi - lo) // 2,
                        (hi - lo) // 100])


def test_the_device_readers_read_the_masked_hash_program(monkeypatch):
    monkeypatch.setattr(peaks, "for_device",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    run = make_run([], {}, {})
    run.config = {"scheme": {"digest_bytes": 1167}}
    assert _reader("challenge.device_ms_per_wave")(run) is None  # untraced
    assert _reader("challenge.hbm_roofline_pct")(run) is None
    run.trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_sha512_masked(7)", 1000, 4_000_000],
            ["jit_sha512_masked(7)", 9_000_000, 6_000_000],
            ["jit_verify_signatures(2)", 20_000_000, 50_000_000]]}]}]}
    run.traced_waves, run.traced_lo_ns, run.traced_hi_ns = 1, 0, 10 ** 9
    assert _reader("challenge.device_ms_per_wave")(run) == pytest.approx(10.0)
    moved = run.quorum * run.wave_size * (2031 + 4 + 64)
    assert _reader("challenge.hbm_roofline_pct")(run) == pytest.approx(
        moved / 0.010 / 819e9 * 100)
    # a program without the kernel (the parent): nothing to read
    run.trace["planes"][0]["lines"][0]["events"] = [
        ["jit_sha512_fixed(7)", 1000, 4_000_000]]
    assert _reader("challenge.device_ms_per_wave")(run) is None
    assert _reader("challenge.hbm_roofline_pct")(run) is None


# -- the cell, rehearsed ---------------------------------------------------------

def test_a_traced_rehearsal_is_correct_and_prints_the_four(
        steer, capsys, monkeypatch):  # noqa: F811
    """At the scheme's REHEARSAL sizes (waves of 8 over 16 wallets; the
    messages keep their lengths). No device number is read from it: the
    device plane is made up."""
    monkeypatch.setattr(harness, "Tracer", _TracerWithTheHash)
    monkeypatch.setattr(peaks, "for_device",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    rc, lines = _run(steer, capsys, CELL, trace=1)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    rows = [ln for ln in lines if ln.get("phase") == "check"][0]["compared"]
    assert rows["party_shapes"]["value"] == ["B8|q3"]
    assert rows["compile_requests_in_window"]["value"] == 0
    waves = [ln for ln in lines if ln.get("phase") == "wave"]
    assert all(w["compile_requests"] == 0 and w["succeeded"] == 8
               for w in waves)  # the unmeasured wave too: the warm batch's rung
    m = {k: v["value"] for k, v in last["metrics"].items()}
    must, may = printed(_manifest(steer), "per_layer", CELL)
    assert must == set(NEW) and must <= set(m) <= may
    # a wave of 8: one message at 1,167 bytes and seven of the mix
    assert (1167 + 7 * 150) / 8 <= m["wire.tx_bytes_per_sign"] <= 1167
    # a manifest carries each payload in hex, with its initiator signature
    assert m["batch.manifest_kb_per_wave"] * 1e3 > 8 * 2 * (
        m["wire.tx_bytes_per_sign"] + 64)
    assert m["challenge.device_ms_per_wave"] > 0
    assert 0 < m["challenge.hbm_roofline_pct"] < 105


def test_with_the_hash_forced_to_the_host_no_result_is_printed(
        steer, capsys, monkeypatch):  # noqa: F811
    """The control: the same cell on a program that hashes the challenge a
    row at a time on the host signs correctly, and the generator refuses
    the run, so nothing is measured under this cell's name."""
    monkeypatch.setenv("MPCIUM_EDDSA_DEVICE_HASH", "0")
    with pytest.raises(RuntimeError, match="hashed on the host"):
        _run(steer, capsys, CELL)
    out = capsys.readouterr().out
    assert '"correct"' not in out
    waves = [json.loads(ln) for ln in out.splitlines()
             if ln.startswith("{") and '"phase": "wave"' in ln]
    assert waves and all(w["succeeded"] == 8 for w in waves)
