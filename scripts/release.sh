#!/usr/bin/env bash
# Build a release: Python wheel + native store server binary.
# The reference's build.sh:16-19 / release.sh:14-22 analogue.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=dist
mkdir -p "$OUT"

# 1. native components (C++ coordination + result store servers)
if [ -d native ]; then
    make -C native -j"$(nproc)"
    cp native/cronsun-stored native/cronsun-logd "$OUT"/ 2>/dev/null || true
fi

# 2. Python wheel (console scripts: cronsun-store/sched/node/web/demo)
python -m pip wheel --no-deps --no-build-isolation -w "$OUT" . \
    || { echo "wheel build unavailable; shipping sdist layout instead";
         tar czf "$OUT/cronsun-tpu-src.tar.gz" cronsun_tpu pyproject.toml README.md; }

ls -l "$OUT"
