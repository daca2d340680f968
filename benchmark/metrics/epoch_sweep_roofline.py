"""The epoch sweep's share of its HBM roofline: the bytes the spec's
epoch processing must read and write, over the sweep programs' device
time times the chip's HBM bandwidth.  The device time is read in the
device stage `process_slots` (one block's slot processing).

The bytes come from the spec's SSZ field widths per validator, not from
the implementation: it reads the registry fields that decide activity,
eligibility, slashing and effective balance, the balance, both
participation flags and the inactivity score, and writes the balance,
the inactivity score and the effective balance.
"""

READ = {
    "effective_balance": 8, "slashed": 1, "activation_eligibility_epoch": 8,
    "activation_epoch": 8, "exit_epoch": 8, "withdrawable_epoch": 8,
    "balance": 8, "previous_epoch_participation": 1,
    "current_epoch_participation": 1, "inactivity_score": 8,
}
WRITE = {"balance": 8, "inactivity_score": 8, "effective_balance": 8}


def epoch_bytes(validators: int) -> int:
    return validators * (sum(READ.values()) + sum(WRITE.values()))


def read(ctx):
    ns = ctx.stage.layer_ns.get("epoch") if ctx.stage else None
    if not ns:
        return None
    seconds = ns / 1e9
    least = epoch_bytes(ctx.cell.config["validators"]) / \
        ctx.peak("hbm_bytes_per_s")
    return 100.0 * least / seconds
