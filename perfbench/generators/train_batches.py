"""Training steps of fixed size: a fresh batch of random tokens a step.

Parameters: ``micro`` sequences a micro-batch, ``n_micro`` micro-batches a
step, ``seq`` tokens a sequence.
"""
import numpy as np


def generate(params: dict, seed: int, seconds: float, limits: dict) -> dict:
    rows = params["micro"] * params["n_micro"]
    seq = params["seq"]
    if seq > limits["max_seq_len"]:
        raise ValueError(f"seq {seq} is beyond the model's "
                         f"{limits['max_seq_len']} positions")

    def batch(step: int) -> np.ndarray:
        rng = np.random.default_rng([seed, step])
        return rng.integers(0, limits["vocab_size"], (rows, seq),
                            dtype=np.int32)

    return {"micro": params["micro"], "n_micro": params["n_micro"],
            "seq": seq, "tokens_per_step": rows * seq, "batch": batch}
