set -x
mkdir -p chiprun_out
# 1. the committed files alone: the new cell, traced, from a git-archive copy of the final tree
( cd _proof/final && time python3 benchmark/run.py --workload hub-175.catchup --seed 2147487701 --seconds 25 --trace 1 > ../../chiprun_out/final.hub-175.json 2> ../../chiprun_out/final.hub-175.err; echo FINAL_RC=$? )
tail -c 1500 chiprun_out/final.hub-175.json; tail -5 chiprun_out/final.hub-175.err
# 2. JAX's persistent-cache keys of a process's first flush, by thread
for m in thread thread main; do
  python tools/proof/first_flush_thread.py $m > chiprun_out/keys.$m.json 2> chiprun_out/keys.$m.err
  grep -E "PERSISTENT COMPILATION CACHE|Writing|Not writing|rror" chiprun_out/keys.$m.err | cut -c1-260 | tail -12
  python3 -c "import json;r=json.loads(open('chiprun_out/keys.$m.json').read().strip().splitlines()[-1]);print(r['mode'],r['flushes'],r['aot'])"
done
