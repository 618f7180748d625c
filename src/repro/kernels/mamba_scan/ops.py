"""Public wrapper for the selective-scan kernel."""

import jax

from repro.kernels.mamba_scan.kernel import selective_scan
from repro.kernels.mamba_scan.ref import selective_scan_ref


def selective_scan_op(dt, a_log, b_ssm, c_ssm, x, d_skip, *, backend: str = "ref", **kw):
    if backend == "pallas":  # interpreted on the CPU only
        return selective_scan(
            dt, a_log, b_ssm, c_ssm, x, d_skip,
            interpret=jax.default_backend() == "cpu", **kw,
        )
    return selective_scan_ref(dt, a_log, b_ssm, c_ssm, x, d_skip)
