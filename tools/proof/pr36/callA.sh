set -x
# PR 36, call A: the new cell's first runs on the chip. One traced run (cold: it compiles or loads commit-10k's programs), one
# untraced, the three controls (each has to read not `correct`), and the parent 0b19e64 (_parent/: a `git archive` copy with this
# PR's BENCHMARK.json and benchmark/ laid over it, as the driver lays them) once: it has to END, with a result or an error.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr36
C=live-10k.vote-commit
show() {
  python - "$1" <<'PY'
import json, sys
try:
    o = json.loads(open(f"chiprun_out/pr36/A.{sys.argv[1]}.out").read().strip().splitlines()[-1])
except Exception as e:
    print(sys.argv[1], "no result line:", e); sys.exit(0)
print(sys.argv[1], "correct", o["correct"], "attempted", o["attempted"], "failed", o["failed"], o["flush"],
      json.dumps({k: v["value"] for k, v in o["metrics"].items()}))
print(sys.argv[1], "checks", json.dumps({k: v[0] for k, v in o["checks"].items() if v[0]}), "first_off_path", o["notes"]["first_off_path"])
print(sys.argv[1], "probes", json.dumps(o["notes"]["entry_probes"]), "judge_s", o["notes"]["judge_s"])
print(sys.argv[1], "device", o["device"], "setup", o["setup_split"], "spans", o["spans_p50"], "cold", o["cold"], "host", o["host"])
if "breakdown" in o:
    print(sys.argv[1], "breakdown", json.dumps(o["breakdown"]))
PY
}
run() {  # name, root, seed, trace, extra...
  name=$1; root=$2; seed=$3; trace=$4; shift 4
  ( cd $root && time timeout 1500 python benchmark/run.py --workload $C --seed $seed --seconds 25 --trace $trace "$@" \
      > /root/repo/chiprun_out/pr36/A.$name.out 2> /root/repo/chiprun_out/pr36/A.$name.err )
  echo RC_$name=$?; grep -E "^check|^benchmark:" chiprun_out/pr36/A.$name.err | tr '\n' ';'; echo; tail -3 chiprun_out/pr36/A.$name.err | cut -c1-600
  show $name
}
run traced . 2147495011 1
run plain . 2147495001 0
run unsent_third . 2147495021 0 --control unsent_third
run memo_answers_all . 2147495022 0 --control memo_answers_all
run counted_unverified . 2147495023 0 --control counted_unverified
run parent _parent 2147495001 0
run parent_traced _parent 2147495011 1
