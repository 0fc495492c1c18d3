"""Shared test configuration.

x64 is enabled globally: the Krylov/statistics layers need double precision
and the model layers pin their dtypes explicitly, so bf16/f32 paths are
unaffected.  NOTE: XLA_FLAGS device-count forcing is deliberately NOT set
here — tests see the 1 real CPU device; multi-device behavior is tested in
subprocesses (tests/test_krylov_distributed.py).
"""
import os
import subprocess
import sys

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest

SUBPROCESS_TIMEOUT_S = 900  # per attempt; matches the historical budget


def run_subprocess_with_retry(script: str, env=None, timeout=None,
                              retries: int = 1):
    """Run a multi-device test script with a per-attempt timeout + retry.

    The 8-forced-host-device subprocess tests occasionally stall on a
    cold XLA compile cache under CI load; one bounded retry (on timeout
    OR nonzero exit — crashes from device-bringup races look like
    failures too) distinguishes that flake from a real hang or a
    deterministic breakage, which fails after the second attempt.
    Returns the last ``CompletedProcess``; raises ``pytest.fail`` with
    the captured output on exhausted attempts.
    """
    timeout = timeout or SUBPROCESS_TIMEOUT_S
    env = dict(env if env is not None else os.environ)
    # the scripts force host devices: CPU by design, and never a
    # contender for an accelerator the test process may hold
    env["JAX_PLATFORMS"] = "cpu"
    last = None
    for attempt in range(retries + 1):
        try:
            last = subprocess.run([sys.executable, "-c", script], env=env,
                                  capture_output=True, text=True,
                                  timeout=timeout)
        except subprocess.TimeoutExpired as e:
            if attempt == retries:
                pytest.fail(
                    f"subprocess timed out twice ({timeout}s per attempt); "
                    f"partial stdout:\n{(e.stdout or b'')[-2000:]}")
            continue
        if last.returncode == 0:
            return last
        if attempt == retries:
            pytest.fail("subprocess failed after retry:\n"
                        + last.stdout[-3000:] + "\n" + last.stderr[-3000:])
    return last


@pytest.fixture
def rng():
    return np.random.default_rng(0)
