set -x
# PR 34, call D (calls C and D in one, after a call that found no machine): the control and three of the planted faults; then the
# committed files alone: a `git archive $(git write-tree)` copy under _proof/final/ with a compile cache of its own inside it, so
# its first run is cold (every program compiled there), its second, traced, finds them; last the fault `roots_set`: it makes most rows of every call invalid, so every call walks the recovery ladder and loads its
# per-signature leaves: a short window, and last in the call.
mkdir -p chiprun_out/pr34
C=light-seq-100.sequence
(
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
seed=2147494451
for X in unsent_third links_unchecked first_header_only roots_powers; do
  time timeout 900 python benchmark/run.py --workload $C --seed $seed --seconds 25 --trace 0 --control $X \
    > chiprun_out/pr34/C.$X.out 2> chiprun_out/pr34/C.$X.err
  echo RC_$X=$?; grep -E "^check|^benchmark:" chiprun_out/pr34/C.$X.err | tr '\n' ';'; echo
  python - $X <<'PY'
import json, sys
o = json.loads(open(f"chiprun_out/pr34/C.{sys.argv[1]}.out").read().strip().splitlines()[-1])
print(sys.argv[1], "correct", o["correct"], "attempted", o["attempted"], "failed", o["failed"], o["flush"], json.dumps(o["notes"]["entry_probes"]), o["notes"]["judge_s"])
PY
  seed=$((seed + 1))
done
)
(
cd _proof/final && unset JAX_COMPILATION_CACHE_DIR && export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache
time timeout 1500 python benchmark/run.py --workload $C --seed 2147494461 --seconds 25 --trace 0 \
  > ../../chiprun_out/pr34/D.final.cold.out 2> ../../chiprun_out/pr34/D.final.cold.err
echo FINAL_COLD_RC=$?; grep -E "^check|^benchmark:" ../../chiprun_out/pr34/D.final.cold.err | tr '\n' ';'; echo; tail -c 2500 ../../chiprun_out/pr34/D.final.cold.out
time timeout 900 python benchmark/run.py --workload $C --seed 2147494462 --seconds 25 --trace 1 \
  > ../../chiprun_out/pr34/D.final.traced.out 2> ../../chiprun_out/pr34/D.final.traced.err
echo FINAL_TRACED_RC=$?; grep -E "^check|^benchmark:" ../../chiprun_out/pr34/D.final.traced.err | tr '\n' ';'; echo; head -c 1800 ../../chiprun_out/pr34/D.final.traced.out
du -sh .jax_cache
)
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
time timeout 1200 python benchmark/run.py --workload $C --seed 2147494459 --seconds 5 --trace 0 --control roots_set \
  > chiprun_out/pr34/C.roots_set.out 2> chiprun_out/pr34/C.roots_set.err
echo RC_roots_set=$?; grep -E "^check|^benchmark:" chiprun_out/pr34/C.roots_set.err | tr '\n' ';'; echo; tail -c 1500 chiprun_out/pr34/C.roots_set.out; tail -c 600 chiprun_out/pr34/C.roots_set.err
