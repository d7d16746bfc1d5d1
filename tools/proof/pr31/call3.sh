set -x
# PR 31, call 3: after call 2 (decompress_rows' plain jit lowers in 4.5 s under the parent's stack, 22-24 s under the
# change's two added frames): the retry is a policy the callers' loops ask, the frames are the parent's. Parity?
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out
k=0
for c in _proof/final _parent _proof/final; do
  k=$((k+1))
  python tools/proof/pr31/lowering.py $c commit-1024.verify-commit > chiprun_out/pr31.lowering3.$k.txt 2>&1
  echo "== $c rc=$?"
  grep -E "^checkout|^call|^total|Traceback|Error" chiprun_out/pr31.lowering3.$k.txt | cut -c1-300
  grep -E "^Finished (jaxpr|XLA)" chiprun_out/pr31.lowering3.$k.txt | awk '{ if ($(NF-1)+0 > 0.5) print }' | cut -c1-300
done
