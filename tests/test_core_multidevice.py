"""Multi-device semantics of the Synkhronos core, via subprocesses with 8
forced host devices (this process keeps 1 device)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_check(name: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable, "-m", "repro.testing.md_checks", name],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, f"{name} failed:\n{r.stdout}\n{r.stderr[-3000:]}"


def test_scatter_reduce():
    run_check("scatter_reduce")


def test_indexing():
    run_check("indexing")


def test_collectives():
    run_check("collectives")


def test_sgd_parity_with_serial_program():
    """Paper Appendix A: the multi-GPU SGD program must match serial SGD."""
    run_check("sgd_parity")


def test_seq_sharded_pos_minor_decode():
    run_check("decode_seq_sharded")


def test_serve_engine_dp_sharded():
    """The serve engine on an 8-way data mesh (slotted, paged with
    preemption, hybrid) matches the single-device engine's greedy tokens,
    and builds nothing after prebuild."""
    run_check("serve_dp_sharded")


def test_elastic_restore():
    """Checkpoint from a dp=8 mesh restores and trains on a dp=4xtp=2 mesh."""
    run_check("elastic")


def test_global_indexing():
    """Regression: device-resident batch= ids are GLOBAL rows; shuffled
    indices crossing shard boundaries must read the right rows, concat
    outputs slice back to the request length (incl. pad > len(idx))."""
    run_check("indexing_global")


def test_bucketed_reduce_matches_monolithic():
    """Bucketed flat all-reduce == monolithic pmean bit-for-bit (fp32)."""
    run_check("bucketed_reduce")


def test_flat_engine_parity():
    """Faithful flat engine and ZeRO flat engine track the legacy GSPMD
    adam step loss-for-loss over several steps on dp=8."""
    run_check("flat_parity")
