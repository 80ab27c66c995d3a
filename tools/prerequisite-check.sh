#!/bin/bash
# Prerequisite check for running minbft-tpu (the reference's
# tools/prerequisite-check.sh probes SGX; this probes the TPU + native
# toolchain story).  Informational: exits 0 unless Python-side
# prerequisites are missing.
#
# It is for a person to read.  Its output and exit status are NOT a
# permission for any program path to continue a chip run: the "no TPU"
# branch below only says that the tests' CPU backend still works.  A
# run that was meant for the chip and finds none fails — `peer run` says
# on stderr which crypto it chose and why, `chip_smoke.py` and
# `python3 -m benchmark.run` exit non-zero.  Nothing in the repo reads
# this script or the probe.
set -u
cd "$(dirname "$0")/.."

echo "== python =="
python -c "import sys; print(sys.version.split()[0])" || exit 1
for mod in jax numpy yaml grpc; do
    python -c "import $mod" 2>/dev/null \
        && echo "module $mod: ok" || { echo "module $mod: MISSING"; exit 1; }
done

echo "== jax backend =="
python - <<'EOF'
import jax
print("default backend:", jax.default_backend())
print("devices:", jax.devices())
EOF

echo "== native toolchain =="
for tool in g++ make; do
    command -v "$tool" >/dev/null && echo "$tool: ok" || echo "$tool: missing (native USIG module unavailable; software USIG still works)"
done

echo "== tpu capability =="
if make -C tools/tpu-capability check-tpu-capability >/dev/null 2>&1; then
    tools/tpu-capability/check-tpu-capability
    case $? in
        0) echo "(accelerator path available)";;
        1) echo "(no TPU here: tests run on the jax CPU backend with JAX_PLATFORMS=cpu; a chip run would FAIL, not fall back)";;
        *) echo "(probe error)";;
    esac
else
    echo "could not build the capability probe (no g++?)"
fi
exit 0
