#!/usr/bin/env bash
# Lint/format gate (reference: format.sh:1-147, yapf+flake8). This build uses
# ruff for both roles. `./format.sh` fixes in place; `./format.sh --check` is
# the CI mode.
set -euo pipefail
cd "$(dirname "$0")"

# ray_lightning_tpu covers the obs/ package; tools/ carries the obs
# snapshot and the chip go/no-go scripts.
TARGETS=(ray_lightning_tpu tests examples tools __graft_entry__.py)

if ! command -v ruff >/dev/null 2>&1; then
    echo "ruff not installed; skipping lint (CI installs it)" >&2
    exit 0
fi

if [[ "${1:-}" == "--check" ]]; then
    ruff check "${TARGETS[@]}"
    ruff format --check "${TARGETS[@]}"
else
    ruff check --fix "${TARGETS[@]}"
    ruff format "${TARGETS[@]}"
fi
