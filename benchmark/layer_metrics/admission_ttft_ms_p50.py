"""Median time to the first token where it is recorded and not held to a
bound, because the reading moves in steps no bound of at most 10% admits
at half its width (my chip runs and the driver's check, PR 22):

- under a mesh the engine admits synchronously, between one-token
  dispatches, so the reading (264 ms) moves by a dispatch's phase, 1.2% from
  run to run;
- in a closed loop of a few clients whose prompts are half one chunk and
  half two, the readings form two clusters (1.0-1.7 s and 3.0-4.4 s), each
  a whole number of 0.33 s ticks, and the median of ~37 of them is the edge
  of a cluster: it reads 3023 ms in most runs and 2697 ms, one tick less, in
  some.

In a closed loop a shorter time to the first token is a shorter cycle, so it
moves ``out_tok_s``, which is judged there; it is also what a mesh path with
fused decode or overlapped admission trades against ``tpot_ms_p50``."""

from benchmark import samples

LAYER = "engine host loop"
DEVICE_METRIC = True


def read(run):
    return samples.ttft_percentile_ms(run, 50.0)
