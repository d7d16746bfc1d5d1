env | grep -i -E "jax|xla|tpu" | cut -c1-200
python3 - <<'PY'
import os, jax
from tendermint_tpu.ops.aot_cache import configure_compile_cache
d = configure_compile_cache()
print("dir", d)
for k in ("jax_compilation_cache_max_size", "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes"):
    print(k, getattr(jax.config, k, "n/a"))
import glob
fs = [(os.path.getsize(f), os.path.basename(f)[:60]) for f in glob.glob(os.path.join(d, "*")) if os.path.isfile(f)]
print("files", len(fs), "MiB", round(sum(s for s, _ in fs) / 2**20, 1))
for s, n in sorted(fs, reverse=True)[:25]:
    print(round(s / 2**20, 1), n)
ex = glob.glob(os.path.join(d, "export", "*"))
print("export files", len(ex), "MiB", round(sum(os.path.getsize(f) for f in ex) / 2**20, 1))
PY
