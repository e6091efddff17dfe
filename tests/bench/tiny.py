"""Small-width copies of the benchmark's cells, for CPU tests."""

from bench import harness


def tiny_spec(name: str, chips: int = 1, compute: str = "float32") -> dict:
    """The cell as BENCHMARK.json states it, at a width and length a CPU
    test can hold: every mechanism and limit kept.  The program computes
    in ``compute`` (float32 by default, so that a sound run of the tests
    sits far inside the limits set for bfloat16 at the cells' widths)."""
    spec = harness.load_cell(name)
    m = spec["config"]["model"]
    gqa = m["n_kv"] < m["n_heads"]
    m.update(n_layers=2, d_model=64, n_heads=4, n_kv=2 if gqa else 4,
             d_head=16, d_ff=128, vocab=503, vocab_pad_to=64, loss_chunk=8,
             compute_dtype=compute)
    spec["traffic"]["trainer"]["seq_len"] = 16
    spec["cell"] = dict(spec["cell"], chips=chips)
    return spec
