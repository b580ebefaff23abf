"""Share of the chips' busy time spent in collective operations: the
summed device time of the all-reduces (and any all-gather, reduce-scatter,
all-to-all or collective-permute) in the traced window over device busy
time, both averaged over the cell's chips.

Under tensor parallelism each layer closes its attention and its MLP with
a psum over the mesh's ``model`` axis, which the compiler lowers to an
all-reduce; a one-chip cell has none, and the metric reports nothing
there. An operation is a collective by its own HLO name or opcode (the
start of the trace event, or the opcode after the result's type), never
by an operand's name: a fusion that reads an all-reduce's output is
compute."""

NAME, UNIT, SOURCE = "collective_frac", "frac", "device_trace"
LAYER = "sharding (parallel/sharding.py, the model-axis psums under shard_map)"
MOVES = "itl_p95_s"
_KINDS = (r"(?:all-reduce|all-gather|reduce-scatter|all-to-all"
          r"|collective-permute)(?:-start|-done)?")
# A TPU trace names an all-reduce after the JAX primitive that made it
# (``%psum.40 = bf16[32,1,5120]{...} all-reduce(...)``): match the opcode too.
KERNELS = {"collective": rf"^%?{_KINDS}\b|\s{_KINDS}\("}


def compute(run):
    if run.trace is None:
        return None
    t = run.trace["kernel_s"].get("collective", 0.0)
    if t <= 0 or run.trace["busy_s"] <= 0:
        return None
    return t / run.trace["busy_s"]
