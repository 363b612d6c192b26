"""What tests/test_windowed.py and tests/test_mellum.py read from a cell's
train step as lowered for TPU on the CPU: the text with its locations, how
often each counted choice was lowered, and what is left under the scopes the
q/k layout kernels and the gated attention kernels emptied."""

import re

from brpc_tpu import obs

LOWERING_COUNTERS = ("attn_kernel_lowerings", "attn_gated_kernel_lowerings",
                     "attn_dense_lowerings",
                     "moe_grouped_lowerings", "moe_rows_lowerings",
                     "qk_layout_kernel_lowerings", "qk_layout_plain_lowerings")


def lowered_for_tpu(trace):
    """``trace()`` traces the step: (the traced step, its text lowered for
    TPU with locations, counter -> how often it was lowered)."""
    obs.set_enabled(True)
    before = [obs.counter(n).get_value() for n in LOWERING_COUNTERS]
    traced = trace()
    text = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    counts = {n: obs.counter(n).get_value() - b
              for n, b in zip(LOWERING_COUNTERS, before)}
    return traced, text, counts


def _named_lines(text):
    """(line, the name its location gives it) of the lowered text."""
    names = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    for line in text.splitlines():
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if at:
            yield line, names.get(at.group(1), "")


def layout_transposes(text) -> dict:
    """Operand type -> how many transposes under ``attn.layout`` take it."""
    found = {}
    for line, name in _named_lines(text):
        if "stablehlo.transpose" in line and "attn.layout" in name:
            operand = re.search(r": \(tensor<([^>]*)>\) ->", line).group(1)
            found[operand] = found.get(operand, 0) + 1
    return found


def float32_heads_under(text, scope: str) -> list:
    """The lines under ``*<scope>`` (``.rope``, ``.out``) that hold a
    float32 [1, 8192, H, D]."""
    return [line for line, name in _named_lines(text)
            if scope in name
            and re.search(r"tensor<1x8192x\d+x\d+xf32>", line)]


def pallas_sites(text) -> list:
    """The kernel name of every Pallas call site of the lowered text."""
    sites = re.findall(r'kernel_name = "(\w+)"', text)
    assert len(sites) == len(re.findall(r"tpu_custom_call", text))
    return sites
