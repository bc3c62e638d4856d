"""The challenge hash's share of the chip's memory roofline: the bytes it
has to read and write for the traced wave, from shapes (``hash_bytes.py``:
q parties × lanes × (row + length + digest), the row as wide as the rung
of the configuration's longest message), over the masked SHA-512
program's device time and the chip's published HBM bandwidth
(``peaks.json``). The vector unit's integer peak is not published, so
memory is the only roofline that can be stated for this kernel: it bounds
the hash's time from below and is no target (the kernel is bound by its
80 rounds of 64-bit integer work a block). None without the kernel."""

from benchmark import hash_bytes, peaks


def read(run):
    if run.trace is None or not run.traced_waves:
        return None
    seconds = run.program_seconds().get(hash_bytes.PROGRAM)
    if not seconds:
        return None
    import jax

    moved = hash_bytes.per_wave(
        run.wave_size, run.quorum,
        run.config["scheme"]["digest_bytes"]) * run.traced_waves
    peak = peaks.for_device(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return moved / seconds / peak * 100.0
