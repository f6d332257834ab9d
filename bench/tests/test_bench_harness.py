"""The harness: cells found by name, generators, statistics, no-TPU exit."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchkit import ROOT

from bench import generators, harness, stats


def _digest(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_traffic_and_metric_found_by_name(tmp_path):
    """A new configuration, traffic mix and per-layer metric are new files
    plus entries in BENCHMARK.json; no file that is there changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path / "bench")

    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((tmp_path / "bench/configs/smollm-360m.json").read_text())
    cfg["name"] = "smollm-360m-copy"
    (tmp_path / "bench/configs/smollm-360m-copy.json").write_text(json.dumps(cfg))
    for group in ("adapters", "refs"):
        src = (tmp_path / f"bench/{group}/smollm-360m.py").read_text()
        (tmp_path / f"bench/{group}/smollm-360m-copy.py").write_text(src)
    traffic = json.loads((tmp_path / "bench/traffic/serve-chat.json").read_text())
    traffic["rate_per_s"] = 1.5
    (tmp_path / "bench/traffic/serve-slow.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/metrics/serve.requests_seen.py").write_text(
        "def read(rec):\n    return rec.get('requests')\n")
    bench["configs"].append({**bench["configs"][0], "name": "smollm-360m-copy",
                             "file": "bench/configs/smollm-360m-copy.json"})
    bench["workloads"].append({"name": "serve-copy-slow", "config": "smollm-360m-copy",
                               "traffic": "serve-slow", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "serve.requests_seen", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine host loop", "moves": "ttft_p95_ms",
                               "workloads": ["serve-copy-slow"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tokens_per_s":
            m["workloads"].append("serve-copy-slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("serve-copy-slow", root=tmp_path)
    assert cell.traffic["rate_per_s"] == 1.5
    assert cell.config_name == "smollm-360m-copy"
    assert cell.adapter().__file__.endswith("adapters/smollm-360m-copy.py")
    assert cell.ref().__file__.endswith("refs/smollm-360m-copy.py")
    assert cell.kind().__file__.endswith("kinds/serve.py")
    assert [m["name"] for m in cell.per_layer] == ["serve.requests_seen"]
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p95_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert cell.metric_reader("serve.requests_seen").read({"requests": 7}) == 7
    changed = {p for p, h in before.items() if _digest(tmp_path / "bench").get(p) != h}
    assert not changed


def test_every_cell_and_metric_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.kind() and cell.ref() and cell.adapter()
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m["name"]).read)


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_generators_repeat_for_a_seed_and_differ_across_seeds(kind):
    big = 2**40 + 123
    if kind == "train":
        a = generators.train_tokens(big, 3, 4, 16, 1000)
        assert np.array_equal(a, generators.train_tokens(big, 3, 4, 16, 1000))
        assert not np.array_equal(a, generators.train_tokens(big + 1, 3, 4, 16, 1000))
        assert not np.array_equal(a, generators.train_tokens(big, 4, 4, 16, 1000))
        assert len({r.tobytes() for r in a}) == a.shape[0]
        return
    traffic = json.loads((ROOT / "bench/traffic/serve-chat.json").read_text())

    def mix(seed):
        return generators.serve_requests(seed, traffic, 1000, 10.0)

    a, b, c = mix(big), mix(big), mix(7)
    assert [(r.due, r.prompt.tobytes(), r.max_new_tokens) for r in a] == \
        [(r.due, r.prompt.tobytes(), r.max_new_tokens) for r in b]
    assert [r.prompt.tobytes() for r in a] != [r.prompt.tobytes() for r in c]
    # the same work at the same times for every seed
    assert [(r.due, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due, r.prompt.size, r.max_new_tokens) for r in c]
    other = generators.serve_requests(
        big, {**traffic, "schedule_seed": traffic["schedule_seed"] + 1}, 1000, 10.0)
    assert [r.prompt.size for r in other] != [r.prompt.size for r in a]
    assert sorted(r.prompt.size for r in other) == sorted(r.prompt.size for r in a)
    win = [r for r in a if r.in_window]
    assert len(win) == round(traffic["rate_per_s"] * 10.0)
    assert all(0.0 <= r.due < 10.0 for r in win)
    assert all(r.due < 0 for r in a if not r.in_window and r.index < win[0].index)
    dues = [r.due for r in a]
    assert dues == sorted(dues)


def test_tails_over_all_samples():
    rng = np.random.default_rng(0)
    x = rng.lognormal(size=1001)
    assert stats.percentile(x, 95) == pytest.approx(np.percentile(x, 95))
    assert stats.percentile([3.0], 95) == 3.0
    # a failed request reads as infinite and drags the tail with it
    inf = float("inf")
    assert stats.percentile([1.0] * 20 + [inf], 95) == 1.0
    assert stats.percentile([1.0] * 19 + [inf], 95) == inf
    assert stats.percentile([1.0] * 18 + [inf, inf], 95) == inf
    # a tail is the tail of all samples, not a median of chunk tails
    chunks = np.array_split(x, 10)
    assert stats.percentile(x, 95) != pytest.approx(
        np.median([np.percentile(c, 95) for c in chunks]))


def test_serve_latencies_and_rate_over_the_whole_window():
    from bench.generators import Request
    from bench.kinds import serve

    def completion(times, n):
        return types.SimpleNamespace(status="ok", tokens=[1] * n, token_times=times)

    base, seconds = 100.0, 10.0
    reqs = [Request(0, -1.0, np.zeros(4, np.int32), 3, False),
            Request(1, 2.0, np.zeros(4, np.int32), 3, True),
            Request(2, 5.0, np.zeros(4, np.int32), 2, True),
            Request(3, 9.5, np.zeros(4, np.int32), 3, True)]
    due = {r.index: base + r.due for r in reqs}
    router = types.SimpleNamespace(
        completions={0: completion([99.5, 100.5, 101.5], 3),
                     1: completion([102.5, 102.6, 102.8], 3),
                     2: completion([105.0, 105.1], 1),   # short of its budget
                     3: completion([109.9, 110.2, 110.4], 3)},
        records={})
    ttft, itl, tokens, failed = serve.latencies(router, reqs, base, seconds, due)
    assert failed == 1
    assert ttft[0] == pytest.approx(500.0) and ttft[2] == pytest.approx(400.0)
    assert ttft[1] == float("inf")          # a failed request misses every limit
    assert len(itl) == 2 + 1 + 2
    assert float("inf") in itl
    # tokens reaching the host inside [base, base + seconds), any request
    assert tokens == 2 + 3 + 2 + 1


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "train-smollm-360m-dp1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_result_line_shape():
    checks = [harness.Check("loss", 1e-4, 1e-3), harness.Check("grad", 2e-3, 1e-3)]
    line = harness.result_line(checks=checks, attempted=5, failed=0,
                               metrics={"setup_s": (12.5, "s")},
                               device={"platform": "tpu"})
    assert line["correct"] is False
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "checks"]
    assert line["checks"]["grad"] == {"value": 2e-3, "limit": 1e-3}
    assert line["metrics"]["setup_s"] == {"value": 12.5, "unit": "s"}
    nan = harness.result_line(checks=[harness.Check("x", float("nan"), 1.0)],
                              attempted=1, failed=0, metrics={}, device={})
    assert nan["correct"] is False
