"""What does the allocator's peak count? Runs one program with a known amount
of temporaries on the chip and prints ``memory_stats()`` before and after,
beside the executable's own ``memory_analysis()``.

    python3 perfbench/tools/memory_probe.py
"""

import json

import jax
import jax.numpy as jnp


def main():
    dev = jax.devices()[0]
    print("device", dev.device_kind, dev.platform)
    print("before", json.dumps(dev.memory_stats()))

    @jax.jit
    def f(x):
        # every sorted copy has to exist at once for the final reduction
        rows = [jnp.sort(x * (i + 1.0), axis=-1) for i in range(6)]
        return sum(jnp.tanh(r).sum() for r in rows) + jnp.stack(rows).max()

    x = jnp.ones((256, 1024, 1024), jnp.float32)  # 1 GiB
    x.block_until_ready()
    print("after the 1 GiB argument", json.dumps(dev.memory_stats()))
    compiled = f.lower(x).compile()
    m = compiled.memory_analysis()
    print("memory_analysis temp GiB", m.temp_size_in_bytes / 2**30,
          "args GiB", m.argument_size_in_bytes / 2**30)
    compiled(x).block_until_ready()
    print("after the run", json.dumps(dev.memory_stats()))


if __name__ == "__main__":
    main()
