#!/usr/bin/env bash
# Tier-1 CI: repo hygiene, the test suite on a forced 8-device host mesh
# (with the full fuzz episode counts), and a relative-link check over
# README.md and docs/.  Speed is measured on the chip by the benchmark in
# BENCHMARK.json + bench/, not here.
#
#   scripts/ci.sh             # full run
#   scripts/ci.sh -k buckets  # extra args forwarded to pytest
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== repo hygiene =="
if git ls-files | grep -q '\.pyc$'; then
  echo "FAIL: compiled bytecode is tracked in git:" >&2
  git ls-files | grep '\.pyc$' >&2
  exit 1
fi
echo "  no tracked *.pyc"

echo "== tier-1 suite (8 forced host devices; 200-episode engine fuzz) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  ENGINE_FUZZ_EPISODES="${ENGINE_FUZZ_EPISODES:-200}" \
  CHAOS_FUZZ_EPISODES="${CHAOS_FUZZ_EPISODES:-6}" \
  ROUTER_FUZZ_EPISODES="${ROUTER_FUZZ_EPISODES:-6}" \
  python -m pytest -x -q "$@"

echo "== docs link check =="
python - <<'EOF'
import os, re, sys
paths = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir("docs") if f.endswith(".md"))
bad = []
for path in paths:
    base = os.path.dirname(path)
    text = open(path).read()
    for m in re.finditer(r"\[[^\]]*\]\(([^)\s#]+)(#[^)]*)?\)", text):
        target = m.group(1)
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        if not os.path.exists(os.path.normpath(os.path.join(base, target))):
            bad.append(f"{path}: {target}")
if bad:
    sys.exit("FAIL: broken relative links:\n  " + "\n  ".join(bad))
print(f"  {len(paths)} files, all relative links resolve")
EOF
echo "CI OK"
