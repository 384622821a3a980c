#!/usr/bin/env bash
# One-time bootstrap — the analog of the reference's startup.sh
# (/root/reference/startup.sh: run once under `srun -n 1` to Pkg-add the
# pinned Julia dependencies, bind the system MPI, and Pkg.build). In this
# framework the Python dependencies (jax/flax/optax/numpy/pytest) ship with
# the image, so bootstrap means: verify the stack is importable and sane,
# build the native C++ host-staging engine, and run the capability smoke
# test (the ROCm-aware ring-exchange PoC was the reference's first runnable
# proof too — README.md:5-7).
#
# Usage:  ./startup.sh            # verify + build native + ring smoke test
#         ./startup.sh --no-test  # skip the smoke test
#
# The chip path is proven separately: `python chip_smoke.py` on a TPU.
set -euo pipefail
cd "$(dirname "$0")"

echo "== dependency check =="
python - <<'EOF'
import importlib
for mod in ("jax", "jax.experimental.pallas", "numpy"):
    importlib.import_module(mod)
    print(f"  {mod}: ok")
import jax
print(f"  jax {jax.__version__}")
EOF
echo "== native host-staging engine =="
bash scripts/build_native.sh

if [ "${1:-}" != "--no-test" ]; then
  echo "== capability smoke test (ring exchange on 8 virtual devices) =="
  python apps/ici_ring_test.py --cpu-devices 8
fi

echo "bootstrap complete"
