# NOTE: deliberately empty of XLA device-count flags — smoke tests and
# benches must see the host's real (single) device; only launch/dryrun.py
# and explicit subprocess tests request 512/8 fake devices.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest  # noqa: E402


@pytest.fixture
def fp64_oracle():
    """Paper Table-1 RMSE methodology as a reusable fixture: enables x64
    for the test body, yields a namespace of fp64 reference builders plus
    the RMSE estimator, and restores the x64 flag on teardown (so the rest
    of the suite keeps fp32 weak-typing).  Used by the kernel sanity test
    and the softmax-state acceptance gates (fp <= 1e-5, int8 <= 6.1e-4,
    fp8 <= 2.2e-3 — the DESIGN.md §13 budgets)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)

    class Oracle:
        @staticmethod
        def decode_ref(q, k, v, length=None, *, scale):
            """fp64 direct-definition decode oracle (inputs upcast)."""
            from repro.kernels.etap.ref import etap_decode_ref
            q64, k64, v64 = (jnp.asarray(a, jnp.float64) for a in (q, k, v))
            return etap_decode_ref(q64, k64, v64, length, scale=scale,
                                   dtype=jnp.float64)

        @staticmethod
        def quant_decode_ref(q, k_codes, k_sz, v_codes, v_sz, length=None,
                             *, scale, dv=0):
            """fp64 oracle for quantized KV: dequantize with the runtime
            definition, then the fp64 direct oracle (same dequant-then-
            slice order as the kernels)."""
            from repro.kernels.etap.ref import dequantize
            k = dequantize(k_codes, k_sz)
            v = dequantize(v_codes, v_sz) if v_codes is not None \
                else k[..., :dv]
            return Oracle.decode_ref(q, k, v, length, scale=scale)

        @staticmethod
        def rmse(out, ref):
            err = np.asarray(out, np.float64) - np.asarray(ref, np.float64)
            return float(np.sqrt(np.mean(err ** 2)))

    try:
        yield Oracle
    finally:
        jax.config.update("jax_enable_x64", prev)


@pytest.fixture(scope="session")
def chip_smoke():
    """The repo-root ``chip_smoke.py`` as a module (its config builder and
    its kernel-vs-XLA comparison), loaded without running it."""
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
