set -x
# PR 34, call G (after the driver refused the entries' place in their lists; call F was the cell alone, traced, named in the call itself):
# the entries appended, `benchmark/tests/conftest.py` new, nothing that a run executes changed. (a) the parent 99f9bcf (_parent/: a
# `git archive` copy with this PR's BENCHMARK.json and benchmark/ laid over it): the new cell has to end at its first warm-up call, exit
# code 1; an accepted cell, traced, has to give its line there; (b) the committed files alone (_proof/final/: `git archive $(git write-tree)`):
# the new cell on three seeds.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr34
C=light-seq-100.sequence
(cd _parent && time timeout 600 python benchmark/run.py --workload $C --seed 2147494611 --seconds 25 --trace 1 \
  > ../chiprun_out/pr34/G.parent.out 2> ../chiprun_out/pr34/G.parent.err; echo PARENT_RC=$?; tail -c 500 ../chiprun_out/pr34/G.parent.err; wc -c ../chiprun_out/pr34/G.parent.out
time timeout 900 python benchmark/run.py --workload hub-175.catchup --seed 2147494612 --seconds 25 --trace 1 \
  > ../chiprun_out/pr34/G.parent.hub.out 2> ../chiprun_out/pr34/G.parent.hub.err; echo PARENT_HUB_RC=$?
grep -E "^check|^benchmark:" ../chiprun_out/pr34/G.parent.hub.err | tr '\n' ';'; echo; head -c 1800 ../chiprun_out/pr34/G.parent.hub.out)
cd _proof/final
for seed in 2147494621 2147494622 2147494623; do
  time timeout 900 python benchmark/run.py --workload $C --seed $seed --seconds 25 --trace 0 \
    > ../../chiprun_out/pr34/G.final.$seed.out 2> ../../chiprun_out/pr34/G.final.$seed.err
  echo FINAL_RC_$seed=$?; grep -E "^check|^benchmark:" ../../chiprun_out/pr34/G.final.$seed.err | tr '\n' ';'; echo
  python - $seed <<'PY'
import json, sys
o = json.loads(open(f"../../chiprun_out/pr34/G.final.{sys.argv[1]}.out").read().strip().splitlines()[-1])
print(sys.argv[1], "correct", o["correct"], "attempted", o["attempted"], "failed", o["failed"], {k: v["value"] for k, v in o["metrics"].items()}, o["flush"], o["checks"])
PY
done
