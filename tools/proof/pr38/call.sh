set -x
# PR 38, the one chip call. Parent 1fc1725 (_parent/: a `git archive` copy with this PR's BENCHMARK.json and benchmark/ laid
# over it, as the driver lays them) against the committed files alone (_proof/final/: a `git archive $(git write-tree)` copy).
# Per cell one traced run a side, the same seed, with what the ring holds after the judge (ring_calls.py); in the two cells
# the new spans are in, the change's slice kept for idle_names.py and the recorder off against on, six pairs of 8 s windows
# in one process (recorder_cost.py); commit-1024 (cold set-up 480 s) on the change alone, last. One compile cache for all.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
O=/root/repo/chiprun_out/pr38
mkdir -p $O
one() {  # side checkout cell seed [keep-trace dir]
  timeout 1200 python tools/proof/pr38/ring_calls.py $2 --workload $3 --seed $4 --seconds 25 --trace 1 \
    ${5:+--keep-trace $5} > $O/$1.$3.out 2> $O/$1.$3.err
  echo "$1 $3 rc=$?"
  grep -v "^check" $O/$1.$3.err | tail -n 3
}
cost() {  # cell seed
  (cd _proof/final && timeout 900 python tools/proof/pr38/recorder_cost.py --workload $1 --seed $2) \
    > $O/cost.$1.out 2> $O/cost.$1.err
  echo "cost $1 rc=$?"; tail -n 2 $O/cost.$1.err
}
one parent _parent live-10k.vote-commit 2147498101
one change _proof/final live-10k.vote-commit 2147498101 $O/slice.live-10k.vote-commit
cost live-10k.vote-commit 2147498102
one parent _parent light-seq-100.sequence 2147498201
one change _proof/final light-seq-100.sequence 2147498201 $O/slice.light-seq-100.sequence
cost light-seq-100.sequence 2147498202
python tools/proof/pr38/idle_names.py $O/slice.live-10k.vote-commit/slice.xplane.pb.gz \
  $O/slice.light-seq-100.sequence/slice.xplane.pb.gz --json $O/idle_names.json > $O/idle_names.md 2>&1
echo "idle_names rc=$?"
python tools/proof/pr38/summary.py $O/*.out
for C in hub-175.catchup commit-10k.verify-commit; do
  S=$([ $C = hub-175.catchup ] && echo 2147498301 || echo 2147498401)
  one parent _parent $C $S
  one change _proof/final $C $S
done
one change _proof/final commit-1024.verify-commit 2147498501
python tools/proof/pr38/summary.py $O/*.out
