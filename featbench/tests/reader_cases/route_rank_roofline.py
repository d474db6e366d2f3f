"""The four-shard store served two pumps inside a 1-s window, of 1 and 2
rows, and one after its end; its route kernel ran 2 us in all, summed
over the four chips, each of which ranks the whole batch, so the kernel
reached 4 x (``route_rank_bytes(1, 4) + route_rank_bytes(2, 4)``) bytes
/ 819 GB/s / 2 us of the HBM roofline.  It is found by the kernel's name
in the op's stats as well as in its own name."""

import roofline

CONFIG = "fraud_accounts_4chip"
CONTEXT = {
    "win": {"end": 1.0,
            "pumps": [(0.11, 0.15, 1), (0.25, 0.3, 2), (1.2, 1.3, 1)]},
    "trace": {"ops": {"custom-call.7": (2e-6, 2, "_route_rank_kernel"),
                      "%fusion = f32[8] fusion(...)": (0.2, 10, "")}},
}
WANT = (100 * 4 * (roofline.route_rank_bytes(1, 4)
                   + roofline.route_rank_bytes(2, 4)) / 819e9 / 2e-6)
VARIANTS = [
    ({"trace": {"ops": {
        "%route_rank_pallas.1 = s32[8,128]{1,0} custom-call(...)":
            (2e-6, 2, "")}}}, WANT),
    ({"trace": {"ops": {}}}, None),
]
