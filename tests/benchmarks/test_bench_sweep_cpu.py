"""One rate of the sweep that finds a cell's knee, on the CPU at a tiny
size: the row it prints."""
import jax

from benchmarks import sweep
from benchmarks.generators import open_deck
from benchmarks.systems import llama_serving

from conftest import load_data


def test_one_rate_of_the_sweep_gives_its_row():
    config, traffic = load_data("tiny-llama.json"), load_data(
        "tiny-chat.json")
    system = llama_serving.System(config, jax.devices()[:1], 5, False)
    system.build()
    system.warm(traffic)
    try:
        row = sweep.offer(system, open_deck, config, traffic, {}, 4.0,
                          2 ** 31 + 5, 2.0)
    finally:
        system.free()
    assert list(row) == [
        "rate_rps", "seconds", "due", "ttft_p50_ms", "ttft_p90_ms",
        "ttft_mean_first_half_ms", "ttft_mean_second_half_ms",
        "no_first_token_at_close", "gaps", "itl_p50_ms", "itl_p95_ms",
        "batch_rows_mean", "batch_rows_mean_first_2s", "step_ms_p50",
        "chunk_step_gap_share"]
    assert row["rate_rps"] == 4.0 and row["seconds"] == 2.0
    assert row["due"] == 8
    # on a loaded test machine the request due in the window's last
    # moments may still wait for its first token at the close
    assert 0 <= row["no_first_token_at_close"] <= 2 and row["gaps"] > 0
    assert 0 < row["ttft_p50_ms"] <= row["ttft_p90_ms"]
    assert 0 < row["itl_p50_ms"] <= row["itl_p95_ms"]
    assert row["batch_rows_mean"] >= 1 and row["step_ms_p50"] > 0
    assert 0 <= row["chunk_step_gap_share"] <= 100
