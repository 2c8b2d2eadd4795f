"""Small cells for the CPU tests: the real cells' paths (1F1B on the
specialized executor on one device; ZB-V on the scan executor over four)
at widths a test run can hold."""

import cell

OPTIMIZER = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, grad_clip=1.0)
# Limits at this size, from CPU readings on four seeds (largest of the
# program / smallest of the fp8 control): one device loss 1.5e-4, grad_norm
# 1.5e-4, grad_leaf 9.8e-4 / 5.4e-3, change_leaf 6.5e-3; four stages loss
# 6.3e-5, grad_norm 6.5e-4 / 4.8e-3, grad_leaf 1.5e-3 / 1.6e-2, change_leaf
# 7.2e-3.  The chip cells' limits are for their own sizes.
SMALL_LIMITS = {
    1: {"loss": 1e-3, "grad_norm": 1e-3, "grad_leaf": 2.5e-3, "change_leaf": 0.05},
    4: {"loss": 1e-3, "grad_norm": 2e-3, "grad_leaf": 5e-3, "change_leaf": 0.05},
}
END_TO_END = [
    {"name": "tokens_per_s", "unit": "tokens/s/chip"},
    {"name": "step_hbm_gb", "unit": "GB"},
    {"name": "setup_s", "unit": "s"},
]


def small_cell(pipe: int, limits=None) -> cell.Cell:
    config = dict(
        name=f"small-p{pipe}",
        source="test",
        architecture="internlm2",
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_hidden_layers=2 if pipe == 1 else 8,
        vocab_size=256,
        initializer_range=0.02,
        rms_norm_eps=1e-6,
        rope_theta=10000,
        torch_dtype="bfloat16",
    )
    traffic = dict(
        pipe=pipe,
        schedule="1f1b" if pipe == 1 else "zb-v",
        executor="specialized" if pipe == 1 else "scan",
        microbatches=4 if pipe == 1 else 12,
        microbatch_size=1,
        seq_len=64,
        tokens={"kind": "successor_chain", "jump_share": 0.15},
        optimizer=OPTIMIZER,
        check_steps=3,
    )
    limits = SMALL_LIMITS[pipe] if limits is None else limits
    return cell.Cell(
        f"small_p{pipe}", pipe, config, cell.architecture(config), traffic, limits, END_TO_END, []
    )
