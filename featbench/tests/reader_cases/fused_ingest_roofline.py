"""In a 1-s window the primary table took two ingest batches, one of 10
rows over 8 keys and 9 (key, bucket) pairs, and one after the window's
end; a wires batch went to a secondary ring and is not counted.  The
kernel's op (named after the jitted ingest that holds it) ran 1 us in all,
so it reached ``fused_ingest_bytes(10, 9, 2)`` / 819 GB/s / 1 us of the
HBM roofline.  A trace without the kernel's op reads nothing."""

import roofline

CONTEXT = {
    "lanes": {"transactions": 2},
    "win": {"end": 1.0,
            "ingests": [("transactions", 0.05, 0.06, 10, 8, 9),
                        ("wires", 0.07, 0.08, 4, 4, 4),
                        ("transactions", 1.1, 1.2, 5, 5, 5)]},
    "trace": {"ops": {"%_ingest_pure.1 = (s32[16,32]{1,0}, ...)":
                      (1e-6, 2, "")}},
}
WANT = 100 * roofline.fused_ingest_bytes(10, 9, 2) / 819e9 / 1e-6
VARIANTS = [({"trace": {"ops": {}}}, None)]
