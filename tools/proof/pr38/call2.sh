set -x
# PR 38, the second chip call: what the recorder costs, and what of it this PR added, in the two cells the new spans are in.
# One process a cell in _proof/final/ (the committed files alone): six rounds of three 10 s windows, the arms' order rotated:
# the recorder off, on without PR 38's additions (`base`), on as committed (recorder_cost.py --arms off,base,on).
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
O=/root/repo/chiprun_out/pr38
mkdir -p $O
for C in live-10k.vote-commit light-seq-100.sequence; do
  S=$([ $C = live-10k.vote-commit ] && echo 2147498111 || echo 2147498211)
  (cd _proof/final && timeout 900 python tools/proof/pr38/recorder_cost.py --workload $C --seed $S \
     --pairs 6 --window 10 --arms off,base,on) > $O/cost3.$C.out 2> $O/cost3.$C.err
  echo "cost3 $C rc=$?"; tail -n 2 $O/cost3.$C.err
  python3 -c "import json,sys; r=json.loads(open(sys.argv[1]).read().strip().splitlines()[-1]); print(json.dumps(r['median_less_ms']), json.dumps(r['share_of_p50']), json.dumps(r['less']))" $O/cost3.$C.out
done
