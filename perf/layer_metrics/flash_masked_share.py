"""``flash_masked_share`` — of the score elements the measured job's flash
kernels compute in a step, the share their masks discard: sub-blocks the
diagonal or the window's edge crosses are computed whole and masked. Static
per compiled program: the program sets, when it traces a flash call with a
window or grouped heads, ``harmony_flash_masked_share{job,kernel}`` and
``harmony_flash_score_elements{job,kernel}`` (the elements one call
computes) from the tiles it runs and the band (harmony_tpu/ops/attention.py
``band_work``). A step calls the kernels of a kind once a block of that
kind (``harmony_model_layers{job,kind}``: windowed kernels in ``swa``
blocks, the others in ``full`` ones; the forward of every block twice
under ``remat``), and the share is the element-weighted mean over those
calls. A program without the gauges (the parent of the PR that added them)
reports nothing."""
SHARE = "harmony_flash_masked_share"
ELEMENTS = "harmony_flash_score_elements"
LAYERS = "harmony_model_layers"
LAYER = "kernels"
UNIT = "%"
SOURCE = "program_counter"


def read(obs):
    jobs = list((obs.get("phases") or {}))
    if not jobs:
        return None
    try:
        from harmony_tpu.metrics.registry import get_registry, parse_exposition
        from perf.layer_metrics._moe_kernels import cell_of

        fams = parse_exposition(get_registry().expose())
        by_kernel = {name: {labels["kernel"]: float(v)
                            for _, labels, v in fams[name]["samples"]
                            if labels.get("job") in jobs}
                     for name in (SHARE, ELEMENTS)}
        blocks = {labels["kind"]: float(v)
                  for _, labels, v in fams[LAYERS]["samples"]
                  if labels.get("job") in jobs}
        remat = bool(cell_of(jobs).job["app_params"].get("remat"))
    except Exception:  # no such gauge: nothing to read
        return None
    computed = masked = 0.0
    for kernel, share in by_kernel[SHARE].items():
        calls = blocks.get("swa" if "_win_" in kernel else "full", 0.0) * (
            2.0 if remat and kernel.endswith("_fwd") else 1.0)
        n = calls * by_kernel[ELEMENTS].get(kernel, 0.0)
        computed += n
        masked += n * share
    return 100.0 * masked / computed if computed > 0 else None
