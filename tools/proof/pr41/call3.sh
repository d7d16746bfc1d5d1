set -x
# The cell under its own configuration, commit-10k-forged, on the chip. Run from the root of a checkout that holds the
# change's committed files in _proof/final/ (a `git archive $(git write-tree)` copy) and the parent's in _parent/ (a
# `git archive` copy of the parent commit with the change's BENCHMARK.json and benchmark/ laid over it).
#   bash tools/proof/pr41/call3.sh --workload commit-10k.tampered
# 1. the change: six untraced runs and one traced; 2. the parent on the same cell, once untraced.
[ "$1" = "--workload" ] && W=$2 || W=commit-10k.tampered
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/_proof/jaxcache}
O=$PWD/chiprun_out/pr41
mkdir -p $O
(cd _proof/final && python -c "
import sys; sys.path.insert(0, 'benchmark'); import spec
print('LINT', spec.lint(spec.load_benchmark('.'), '.', 'benchmark'))")
(cd _proof/final && time python benchmark/prove.py --workload $W \
  --seeds 2147541301,2147541302,2147541303,2147541304,2147541305,2147541306 \
  --trace-seeds 2147541311 --out $O/tampered3.jsonl)
echo CHANGE_RC=$?
(cd _parent && time python benchmark/prove.py --workload $W --seeds 2147541321 --out $O/parent3.jsonl)
echo PARENT_RC=$?
python tools/proof/pr41/show.py $O/tampered3.jsonl $O/parent3.jsonl
