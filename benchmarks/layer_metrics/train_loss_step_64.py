"""The loss on the 64th timed step line (state step 69 after 5 warm-up
steps): the guard on the arithmetic. With one seed it repeats exactly, so
a change that buys speed with precision or skipped work shows here when
parent and change run the same seed. Across seeds it spreads too widely
for a bound (vgg16: 4.98 against 5.43, seeds 1 and 2), which is why it is
not an end-to-end metric. Read by the benchmark's tee from the line the
program prints after fetching the value from the device (three decimals).
"""

LAYER = "step_program"
UNIT = "loss"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_sec"

STEP = 64


def read(run):
  return run.loss_at(STEP)
