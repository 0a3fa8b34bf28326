"""How large a vmapped cohort one TPU v5e holds, by compiling the
learner's cohort update for a described (not attached) v5e.

    JAX_PLATFORMS=cpu python benchmarks/chip/size_cohort.py \
        smollm-135m 3 4 5

The first argument names a configuration under ``configs/``; the model is
the one its ``model_ref`` resolves to in the program.

Builds the same program ``RealLearner`` runs for a sync cohort of K
clients (``jit(vmap(client_update))`` over float32 params, K x 8 steps x
batch 8 x seq 64), compiles it for one chip of a described ``v5e:2x2``
topology, and prints ``memory_analysis()`` per K. FedAdam's two float32
moments and the params themselves live beside it (3 x params x 4 bytes).
Nothing runs, so nothing is timed.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

GIB = 2 ** 30


def main(argv) -> int:
    from repro.api import ModelRef
    from repro.federated.client import make_client_update
    from repro.models import get_model
    arch, ks = argv[0], [int(k) for k in argv[1:]] or [3, 4, 5]
    conf = json.loads((HERE / "configs" / f"{arch}.json").read_text())
    cfg = ModelRef.from_dict(conf["model_ref"]).resolve()
    steps, batch, seq = (conf["max_client_steps"],
                         conf["client_batch_size"], conf["seq_len"])
    model = get_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               dtype=jnp.float32)[0])
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
              for k, v in shapes.items()}
    n = sum(v.size for v in shapes.values())
    print(f"{arch}: {n} params, params + FedAdam moments "
          f"{3 * 4 * n / GIB:.2f} GiB")
    update = jax.jit(jax.vmap(make_client_update(model.loss, 0.1),
                              in_axes=(None, 0, 0)))
    for k in ks:
        def arr(*s, dt=jnp.int32):
            return jax.ShapeDtypeStruct(s, dt, sharding=one)
        cohort = {"tokens": arr(k, steps, batch, seq),
                  "labels": arr(k, steps, batch, seq),
                  "mask": arr(k, steps, batch, seq - 1, dt=jnp.float32)}
        if cfg.char_vocab:
            cohort["chars"] = arr(k, steps, batch, seq, cfg.max_word_len)
        mem = update.lower(params, cohort,
                           arr(k, steps, dt=jnp.float32)).compile() \
            .memory_analysis()
        total = (mem.temp_size_in_bytes + mem.output_size_in_bytes
                 + mem.argument_size_in_bytes)
        print(f"K={k}: compiled {total / GIB:.2f} GiB (temp "
              f"{mem.temp_size_in_bytes / GIB:.2f}, out "
              f"{mem.output_size_in_bytes / GIB:.2f}, args "
              f"{mem.argument_size_in_bytes / GIB:.2f})", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
