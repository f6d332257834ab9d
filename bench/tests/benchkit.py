"""Shared helpers of the benchmark's tests (a plain module, not a
conftest): the repository's root and the program on the import path, and
cells cut to a size the CPU runs in seconds."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = dict(hidden_size=48, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=3, num_key_value_heads=1, head_dim=16,
                  vocab_size=256)
# limits at this size, set as the cells' own are, from the program's and the
# control's readings here (program: loss 4e-5, grad 2e-3, update 2e-3,
# logit gap 2e-3; fp8 control: 3e-4, 3e-2, 8e-3, 5e-2 and more)
TINY_TRAFFIC = {
    "train": dict(seq=32, rows_per_chip=4, ref_block_rows=2,
                  limits={"loss": 1.5e-4, "grad": 8e-3, "update": 5e-3}),
    "serve": dict(rate_per_s=20, warmup_s=0.5, drain_s=30, lanes=4, max_len=64,
                  prompt={"median": 12, "sigma": 0.8, "min": 4, "max": 40},
                  output={"median": 8, "sigma": 0.6, "min": 2, "max": 20},
                  limits={"logit_gap": 0.02}),
}


def tiny(name: str, **model):
    """Cell ``name`` of BENCHMARK.json at the tiny size."""
    from bench import harness

    cell = harness.load_cell(name)
    cell.config.update(TINY_MODEL, **model)
    cell.traffic.update(TINY_TRAFFIC[cell.traffic["kind"]])
    return cell

