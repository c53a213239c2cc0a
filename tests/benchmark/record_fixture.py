"""Records fixtures/small.xplane.pb on a chip: `chiprun -- python3
tests/benchmark/record_fixture.py`, then copy chiprun_out/small.xplane.pb
into fixtures/. A few small matmul steps under the harness's spans, with a
sleep between steps so the idle gaps have an owner."""

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import run, trace_reduce

    run.gate(1)

    @jax.jit
    def step(x):
        def body(carry, _):
            return jnp.tanh(carry @ carry), None

        return jax.lax.scan(body, x, None, length=3)[0]

    x = jnp.ones((256, 256), jnp.bfloat16) * 0.01
    jax.block_until_ready(step(x))
    tracer = run.Tracer(True)
    with tracer:
        with tracer.span("window"):
            for _ in range(4):
                with tracer.span("step"):
                    jax.block_until_ready(step(x))
                with tracer.span("sleep"):
                    time.sleep(0.002)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    path = trace_reduce.find_xplane(tracer.dir)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    print(os.path.getsize(path), trace_reduce.summarize(trace_reduce.load(path))["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
