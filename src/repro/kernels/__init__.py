# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

"""Shared VMEM-budget accounting for the ``impl="auto"`` dispatchers.

Every Pallas TPU kernel in this package keeps some per-row working set
resident in VMEM (~16 MiB per core).  Whether a given call fits — and,
for grid-tiled kernels, how many rows each grid step may keep resident —
is the SAME calculation everywhere: count the (rows, 128) f32/i32 arrays
the kernel body holds live at once, multiply by the row stride, divide
the budget.  Each dispatcher states its own array count (that part is
kernel knowledge); the budget arithmetic lives here so no dispatcher
hides a magic row cap.

Used by :mod:`repro.kernels.window_agg.ops` (grid tile sizing — the fold
kernel streams tiles, so there is no row *cap*, only a tile size) and
:mod:`repro.kernels.route.ops` (whole-batch residency cap).
"""

VMEM_BYTES = 16 * 1024 * 1024  # per-core VMEM, TPU v4/v5-class parts

KERNEL_LANE = 128  # native f32/i32 lane width; kernel rows are (8, 128) tiles


def vmem_row_budget(
    live_arrays: int,
    bytes_per_elem: int = 4,
    lane: int = KERNEL_LANE,
    budget: int = VMEM_BYTES,
) -> int:
    """Largest power-of-two row count whose working set fits ``budget``.

    ``live_arrays`` is the number of (rows, lane) arrays the kernel holds
    live at once — pipelined input blocks count twice (double buffering),
    scratch and output tiles once each, plus the body's largest
    simultaneous set of temporaries.  Power-of-two so shape buckets and
    grid tilings stay pow2-aligned (compile caching, exact row shifts).
    """
    per_row = max(live_arrays, 1) * lane * bytes_per_elem
    rows = budget // per_row
    if rows <= 0:
        return 0
    return 1 << (rows.bit_length() - 1)


def note_dispatch(kernel: str, impl: str) -> None:
    """Count an ``impl="auto"`` resolution into ``kernel_dispatch_total``.

    Every kernel entry point records which implementation it actually
    dispatched — a silent XLA fallback on TPU is exactly the regression
    this metric exists to surface.  Called from the un-jitted dispatch
    wrappers, so under an outer ``jit`` it counts once per trace (the
    decision is trace-time anyway); from host-driven call sites it counts
    per call.
    """
    from repro.obs.telemetry import get_telemetry

    get_telemetry().metrics.counter(
        "kernel_dispatch_total",
        "kernel entry-point dispatches by resolved implementation",
        "1",
        labels=("kernel", "impl"),
    ).inc(1.0, kernel=kernel, impl=impl)


def note_cutover(kernel: str) -> None:
    """Count a size cut-over: ``impl="auto"`` chose the kernel's backend
    but the call outgrew it and went to XLA (``kernel_cutover_total``)."""
    from repro.obs.telemetry import get_telemetry

    get_telemetry().metrics.counter(
        "kernel_cutover_total",
        "kernel calls sent to XLA by a size cut-over on the kernel's backend",
        "1",
        labels=("kernel",),
    ).inc(1.0, kernel=kernel)
