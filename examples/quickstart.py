"""Quickstart: train a tiny LM, checkpoint it, then serve it through the
G-TRAC trust-routed pipeline — the whole stack in ~2 minutes on CPU.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data.pipeline import DataConfig, SyntheticLMStream
from repro.models.api import build_model
from repro.serving.gtrac_serve import GTRACPipelineServer
from repro.trainer import optimizer as opt
from repro.trainer.checkpoint import CheckpointManager
from repro.trainer.train_loop import make_train_step


def main():
    # 1. a tiny GPT-2-family model (the paper's arch family, reduced)
    cfg = get_config("gpt2-large").reduced(num_layers=4, vocab_size=256,
                                           remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # 2. train a few steps on the synthetic packed LM stream
    tcfg = TrainConfig(warmup_steps=2, total_steps=20)
    step = jax.jit(make_train_step(model, tcfg))
    data = SyntheticLMStream(DataConfig(cfg.vocab_size, seq_len=64,
                                        global_batch=8))
    opt_state = opt.init(params)
    for i, batch in enumerate(data.batches(0, 20)):
        params, opt_state, m = step(params, opt_state,
                                    {k: jnp.asarray(v)
                                     for k, v in batch.items()})
        if (i + 1) % 5 == 0:
            print(f"step {i+1:3d} loss {float(m['loss']):.3f}")

    # 3. checkpoint + restore round trip
    ck = CheckpointManager("/tmp/repro_quickstart", keep=2)
    ck.save(20, {"params": params}, async_write=True)
    params = ck.restore({"params": params})["params"]
    print("checkpointed + restored at step", ck.latest_step())

    # 4. serve through the trust-aware routed pipeline (2 layers/peer,
    #    adversarial peer mix) — real stage compute, simulated failures
    srv = GTRACPipelineServer(cfg, params, layers_per_stage=2,
                              replicas={"honeypot": 2, "golden": 2,
                                        "turtle": 1},
                              seed=0)
    for rid in range(3):
        out, met = srv.generate(np.arange(1, 9), max_new_tokens=8,
                                request_id=rid)
        print(f"request {rid}: tokens={list(out)} repairs={met.repairs} "
              f"failures={met.failures}")
    print("OK")


if __name__ == "__main__":
    main()
