set -x
mkdir -p chiprun_out
for m in thread thread main; do
  python tools/proof/first_flush_thread.py $m > chiprun_out/keys.$m.json 2> chiprun_out/keys.$m.err
  grep -E "PERSISTENT COMPILATION CACHE|Writing|Not writing|Error|rror reading|cache key" chiprun_out/keys.$m.err | cut -c1-260 | tail -12
  python3 -c "import json;r=json.loads(open('chiprun_out/keys.$m.json').read().strip().splitlines()[-1]);print(r['mode'],r['flushes'],r['aot'])"
done
