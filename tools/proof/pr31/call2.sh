set -x
# PR 31, call 2: call 1 read the change's warm set-up at 1024 at 137.6 s for the parent's 60.2, all of it in
# JAX's jaxpr_to_mlir_module (108 s for 30; 3.6 in the control's run from the root). Which function, and does it follow the checkout?
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out
k=0
for c in _parent _proof/final . _parent _proof/final; do
  k=$((k+1))
  python tools/proof/pr31/lowering.py $c commit-1024.verify-commit > chiprun_out/pr31.lowering.$k.txt 2>&1
  echo "== $c rc=$?"
  grep -E "^checkout|^call|^EVENT|^total|Traceback|Error" chiprun_out/pr31.lowering.$k.txt | cut -c1-300
  grep -E "^Finished (jaxpr|XLA)" chiprun_out/pr31.lowering.$k.txt | awk '{ if ($(NF-1)+0 > 0.5) print }' | cut -c1-300
done
