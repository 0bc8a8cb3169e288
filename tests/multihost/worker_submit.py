"""Every rank of a real ``jax.distributed`` mesh builds a ``ServingEngine``
and submits to it: the request's key is folded on the rank's own CPU
device. (The first entry of the global device list is rank 0's, and no
other rank can put an array there: ISSUE 25's review.)"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
    __file__)), os.pardir, os.pardir, "tools"))
import mp_mesh  # noqa: E402


def main():
    out_dir = sys.argv[1]
    rank, world = mp_mesh.init()
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt_tiny
    from paddle_tpu.serving import ServingConfig, ServingEngine

    assert jax.process_count() == world == 2
    paddle.seed(0)
    net = gpt_tiny()
    net.eval()
    eng = ServingEngine(net, ServingConfig(num_slots=2, page_size=8, seed=7))
    assert eng._base_key.devices() <= set(jax.local_devices())
    rid = eng.submit(np.arange(5, dtype=np.int32), 2)
    want = np.asarray(jax.random.fold_in(jax.random.PRNGKey(7), rid))
    assert eng._requests[rid].key.tolist() == want.tolist()
    oks = [os.path.join(out_dir, f"ok.{r}") for r in range(world)]
    if rank == 0:
        mp_mesh.finish_last(oks[0], oks[1:])
    mp_mesh.finish(oks[rank])


if __name__ == "__main__":
    main()
