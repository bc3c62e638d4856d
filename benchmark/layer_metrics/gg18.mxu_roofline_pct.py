"""The MXU's share of its published peak in the GG18 programs that hold
modular products: of the wave's limb multiply-adds, those the default
strategy on the chip puts on the MXU (Barrett's two constant products of
every modular multiplication and the products by a constant; the product
of two lane values runs on the vector unit: ``ops/pallas_mulmod.py``), the
useful ones from shapes (``schemes/secp256k1_opcounts.py``
``mxu_per_wave``), times 2 operations each, over those programs' device
time in the traced wave and the chip's bf16 peak (``peaks.json``)."""

from benchmark import peaks


def read(run):
    count = getattr(run.scheme, "mxu_ops_per_wave", None)
    if count is None or run.trace is None or not run.traced_waves:
        return None
    import jax

    mxu = {k: v for k, v in count(run.wave_size, run.quorum).items() if v}
    seconds = sum(v for k, v in run.kernel_program_seconds().items()
                  if k[len("jit_"):] in mxu)
    if seconds <= 0:
        return None
    peak = peaks.for_device(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    return 2.0 * sum(mxu.values()) * run.traced_waves / seconds / peak * 100.0
