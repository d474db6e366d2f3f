"""In a 1-s window the primary table took two ingest batches, one of 10
rows over 8 keys and 9 (key, bucket) pairs, and one after the window's
end; a wires batch went to a secondary ring and is not counted.  The
kernel's op, the Mosaic custom call inside the jitted ingest program, ran
1 us in all, so it reached ``fused_ingest_bytes(10, 9, 2)`` / 819 GB/s /
1 us of the HBM roofline; the program's other ops are not the kernel.  A
trace without the kernel reads nothing (the four-chip op's name is
tested in ``test_featbench_parts.py``)."""

import roofline

MOSAIC = 'custom_call_target="tpu_custom_call"'
CONTEXT = {
    "lanes": {"transactions": 2},
    "win": {"end": 1.0,
            "ingests": [("transactions", 0.05, 0.06, 10, 8, 9),
                        ("wires", 0.07, 0.08, 4, 4, 4),
                        ("transactions", 1.1, 1.2, 5, 5, 5)]},
    "trace": {"module_ops": {"jit__ingest_pure": {
        f"%_ingest_pure.1 = (s32[16,32]{{1,0}}, ...) custom-call(...), {MOSAIC}":
            (1e-6, 2, ""),
        "%fusion.3 = s32[64]{0} fusion(...)": (5e-6, 2, "")}}},
}
WANT = 100 * roofline.fused_ingest_bytes(10, 9, 2) / 819e9 / 1e-6
VARIANTS = [({"trace": {"module_ops": {}}}, None)]
