"""End-to-end language-model training through the framework.

The reference's demos stop at k-means driver loops with state re-embedded
as constants each round (``kmeans.py:85-148``); it has no training loop,
no checkpointing, no model zoo. This demo is the TPU-native framework
doing what that design could not: every subsystem in one workload —

 - the **frame layer** as the data path: the token corpus is a
   ``TensorFrame`` whose partitions are the batches (the reference's
   map-over-partitions pattern, ``DebugRowOps.scala:372-386``, reused as
   a data loader);
 - the **mesh train step**: ``TransformerLM.make_sharded_train_step``
   compiles ONE SPMD program (adam + tensor-parallel params +
   data-parallel batch) over a ``data`` × ``model`` device mesh;
 - **checkpoint / resume**: ``utils.checkpoint.save_step`` /
   ``restore_step`` — stop anywhere, resume on the same mesh with every
   shard restored to its device, and continue as if never interrupted.

The task is next-token prediction on modular-increment sequences
(``tokens[t+1] = (tokens[t] + step) % vocab`` with a per-sequence step of
1 or 2): a two-layer model drives loss down an order of magnitude in a
few dozen steps, so correctness shows up as learning, fast, on CPU.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m demos.train_lm
"""

from __future__ import annotations

import os
import tempfile
from typing import Dict, List, Optional, Tuple

import numpy as np

import tensorframes_tpu as tft
from tensorframes_tpu.models import TransformerConfig, TransformerLM
from tensorframes_tpu.parallel.mesh import DeviceMesh
from tensorframes_tpu.utils import checkpoint as ckpt_lib

__all__ = ["corpus_frame", "train", "main"]


def corpus_frame(n_batches: int, batch: int, seq_len: int,
                 vocab: int, seed: int = 0) -> "tft.TensorFrame":
    """The training corpus AS A FRAME: one partition per batch.

    Each row is one training sequence (``seq_len + 1`` tokens: inputs are
    ``[:-1]``, targets ``[1:]``). Partition-per-batch makes the frame's
    ``blocks()`` iterator the data loader.
    """
    rng = np.random.default_rng(seed)
    n = n_batches * batch
    starts = rng.integers(0, vocab, (n, 1))
    steps = rng.integers(1, 3, (n, 1))          # +1 or +2 sequences
    pos = np.arange(seq_len + 1)[None, :]
    toks = (starts + steps * pos) % vocab
    df = tft.analyze(tft.frame({"tokens": toks.astype(np.int64)},
                               num_partitions=n_batches))
    df.cache()
    return df


def _batches(df) -> List[np.ndarray]:
    return [b.dense("tokens").astype(np.int32) for b in df.blocks()]


def train(mesh: DeviceMesh, *, n_steps: int = 40, batch: int = 16,
          seq_len: int = 32, vocab: int = 64,
          checkpoint_root: Optional[str] = None,
          checkpoint_every: int = 0,
          resume: bool = False,
          config: Optional[TransformerConfig] = None,
          learning_rate: float = 3e-3) -> Tuple[Dict, List[float]]:
    """Train on ``mesh``; returns ``(final_state, per-step losses)``.

    With ``checkpoint_root`` + ``checkpoint_every``, saves the train state
    every C steps; with ``resume=True``, restores the latest step first
    and continues from there (cold start when nothing is saved).
    """
    cfg = config or TransformerConfig(
        vocab_size=vocab, d_model=64, n_heads=8, n_layers=2, d_ff=128)
    model = TransformerLM(cfg)
    model_axis = "model" if "model" in mesh.axis_names else None
    step, init_state = model.make_sharded_train_step(
        mesh, data_axis=mesh.data_axis, model_axis=model_axis,
        learning_rate=learning_rate)

    state = init_state()
    start = 0
    if resume and checkpoint_root:
        restored, at = ckpt_lib.restore_step(checkpoint_root, state)
        if restored is not None:
            state, start = restored, at
    if start >= n_steps:
        return state, []

    df = corpus_frame(n_batches=8, batch=batch, seq_len=seq_len,
                      vocab=vocab)
    data = _batches(df)

    losses: List[float] = []
    for i in range(start, n_steps):
        toks = data[i % len(data)]
        state, loss = step(state, toks[:, :-1], toks[:, 1:])
        losses.append(float(loss))
        if (checkpoint_root and checkpoint_every
                and (i + 1) % checkpoint_every == 0):
            ckpt_lib.save_step(checkpoint_root, i + 1, state)
    return state, losses


def main() -> Dict:
    from tensorframes_tpu.parallel.mesh import local_mesh

    mesh = local_mesh()  # every visible device on the data axis
    root = os.path.join(tempfile.mkdtemp(prefix="tft_lm_"), "ckpt")

    # phase 1: train 30 steps, checkpointing every 10
    _, losses = train(mesh, n_steps=30, checkpoint_root=root,
                      checkpoint_every=10)
    resumed_from = ckpt_lib.latest_step(root)
    # phase 2: "crash" after step 30, resume from disk, finish to 40
    state, more = train(mesh, n_steps=40, checkpoint_root=root,
                        checkpoint_every=10, resume=True)

    first, last = losses[0], more[-1]
    print(f"step   1: loss {first:.4f}")
    print(f"step  40: loss {last:.4f}  (resumed from step "
          f"{resumed_from} checkpoint)")
    assert last < first / 3, (first, last)

    # and the trained model actually speaks the language: greedily
    # continue a +1 sequence with the KV-cache decode loop
    import jax
    import jax.numpy as jnp

    cfg = TransformerConfig(vocab_size=64, d_model=64, n_heads=8,
                            n_layers=2, d_ff=128)
    model = TransformerLM(cfg)
    params = jax.device_put(state["params"])
    prompt = jnp.asarray([[10 + i for i in range(8)]], jnp.int32)
    out = model.generate(params, prompt, max_new_tokens=8)
    completion = np.asarray(out[0, 8:]).tolist()
    print(f"prompt 10..17 -> continuation {completion}")
    return {"first_loss": first, "final_loss": last,
            "resumed_from": 30, "total_steps": 40,
            "continuation": completion}


if __name__ == "__main__":
    from tensorframes_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    main()
