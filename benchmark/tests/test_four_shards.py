"""A whole run of a four-shard, four-chip cell on the CPU, over four forced
host devices, with the chip check skipped: the 1of4 configuration at
20,000 passages in `number_of_shards: 4`, the `or-closed1` mix. Sound, it
comes out correct with no failure; with one of test_faults.py's broken
answers it comes out false.

The run needs its own process, since the device count is fixed when JAX
starts: the test runs this file as a script, which prints one JSON line
last."""

import copy
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "msmarco4shard-test.or-closed1"
SEED = 2**33 + 17


def _four_shard_kw() -> dict:
    """test_faults.py's small run, as a four-shard, four-chip cell."""
    from test_faults import _small

    kw, _ = _small()
    spec = copy.deepcopy(kw["spec"])
    base = next(w for w in spec["workloads"] if w["traffic"] == "or-closed1")
    spec["workloads"].append(dict(base, name=CELL, chips=4))
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    kw["config"]["index"]["settings"]["index"]["number_of_shards"] = 4
    return dict(kw, spec=spec)


def main() -> None:
    sys.path[:0] = [ROOT, HERE]
    import jax

    from benchmark import run
    from elasticsearch_tpu.node import Node
    from test_faults import _every_tenth, _score

    assert len(jax.devices()) == 4, jax.devices()
    kw = _four_shard_kw()
    sound = run.run(CELL, SEED, 3.0, False, **kw)
    Node.search = _every_tenth(_score)
    broken = run.run(CELL, SEED, 3.0, False, **kw)
    print(json.dumps({"sound": sound, "broken": broken}), flush=True)


def test_four_shards_on_four_devices_sound_and_broken():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert "docs per shard [5040, 4995, 4965, 5000]" in p.stdout, p.stdout
    # One SPMD program over the four devices serves every search.
    assert p.stdout.count("launches by backend: {'mesh_spmd': ") == 2, p.stdout
    sound, broken = out["sound"], out["broken"]
    assert sound["correct"] and sound["failed"] == 0, sound["checks"]
    assert sound["attempted"] > 20 and sound["device"]["count"] == 4
    assert not broken["correct"], broken["checks"]


if __name__ == "__main__":
    main()
