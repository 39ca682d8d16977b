"""Whether a tenant's dense model table is stored tile-exact.

A dense table is stored block-major, ``[num_blocks, block_size, *value]``.
On the TPU its rows tile by eight: a ``block_size`` that is not a multiple
of 8, or a tail block padded past ``capacity``, makes the compiler store the
table dimension-permuted and every whole-table pull and push relay it out
(PERF.md §6, PR 26: six whole-table copies a step under blocks of 9 rows).
Static per table, so the record is made once, where the job's table is
created or restored (jobserver/entity.py):

  * ``harmony_table_tile_exact{job,table}`` — 1 when ``block_size % 8 == 0``
    and no tail rows, else 0;
  * ``harmony_table_blocks{job,table}`` — the table's block count: what
    any host work that walks the ownership map grows with (PERF.md §6,
    PR 32: an epoch's bookkeeping cost 85-100 ns a block until the
    per-executor counts were kept as ownership changes); read it beside
    the ``window.bookkeeping`` span's seconds;
  * STATUS ``tenants.<job>.table_layout`` = ``{block_size, blocks,
    tail_rows, section_stride, rows, tile_exact}`` (a row of the tenant
    ledger, metrics/accounting.py ``set_table_layout``); ``section_stride``
    is the trainer's where it has one (``PyTreeTrainer.section_stride``:
    rows between the ``[params | m | v]`` sections), else ``None``;
  * STATUS ``tenants.<job>.table_layout.leaf_layout`` = ``{leaves,
    leaf_copies, leaf_bitcasts, pad_rows, rows, fold_pieces,
    direct_rows}`` and the gauge
    ``harmony_table_leaf_pad_share{job,table}`` (``pad_rows / rows``), for
    a trainer whose model lies in its table leaf by leaf
    (``PyTreeTrainer.leaf_rows``: every leaf a range of whole 8-row tiles
    of a section): the leaves, how many of them a step relays out — one
    copy each a direction — and how many ARE their rows (a last dimension
    of ``row_width``), the rows of a section that hold no parameter,
    the price of leaf-aligned rows (PERF.md §6, PR 42), and how the
    gradient comes back: ``fold_pieces`` operands of the fold, of whose
    rows ``direct_rows`` are one leaf's own, read where its relayout left
    them, the others small leaves joined by one concatenate (PR 47).

And what a keyed tenant's push lowers to, recorded where its step program is
built (dolphin/worker.py ``_build_step``; ``TableSpec.push_lowering``):

  * ``harmony_table_push_pallas_rows{job,table}`` — 1 when the keyed push
    is the in-place Pallas row scatter-add (ops.sparse.scatter_add_rows),
    0 for any other lowering;
  * STATUS ``tenants.<job>.table_layout.push_lowering`` = ``"pallas_rows"``
    / ``"xla"``.

And how a pull-all tenant's step applies its update, recorded at the same
place (dolphin/worker.py ``update_lowering``; ``TableSpec.fold_lowering``):

  * ``harmony_table_update_row_ranges{job,table}`` — 1 when the update rule
    runs in the PUSH stage on the stored rows, section by section
    (``TableSpec.fold_row_sections``), 0 when ``compute``'s whole-table
    delta goes through ``push_all``;
  * STATUS ``tenants.<job>.table_layout.update_lowering`` =
    ``"row_ranges"`` / ``"whole_delta"``;
  * ``harmony_table_fold_pallas_sections{job,table}`` / STATUS
    ``table_layout.fold_lowering`` (``row_ranges`` tenants only) — 1 /
    ``"pallas_sections"`` when that fold is the one-pass in-place kernel
    (ops.sections.fold_row_sections), 0 / ``"xla"`` when XLA rewrites the
    sections through fresh buffers;
  * ``harmony_table_fold_direct_row_share{job,table}`` — ``direct_rows /
    rows`` of the trainer's ``leaf_layout`` where the fold is that kernel
    (it reads each piece where it lies), 0 where XLA's takes the pieces
    concatenated: the share of the gradient that is written once on its
    way to the fold.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from harmony_tpu.config.params import TILE_ROWS


def _family():
    from harmony_tpu.metrics.registry import get_registry

    return get_registry().gauge(
        "harmony_table_tile_exact",
        "1 when a dense table's blocks are whole 8-row tiles with no tail "
        "rows (pull_all / push_all move nothing), else 0",
        ("job", "table"))


def note(job: str, spec, section_stride: Optional[int] = None,
         leaf_layout: Optional[Dict[str, int]] = None) -> Dict[str, Any]:
    """Record the storage layout of ``spec`` (a dense ``TableSpec``) as
    ``job``'s model table — and, where the trainer has one, where its
    leaves lie in a section (``LeafRows.record()``); returns the STATUS
    row."""
    from harmony_tpu.metrics.accounting import ledger
    from harmony_tpu.metrics.registry import get_registry

    rows = spec.num_blocks * spec.block_size
    tail = rows - spec.config.capacity
    row = {"block_size": spec.block_size, "blocks": spec.num_blocks,
           "tail_rows": tail, "section_stride": section_stride, "rows": rows,
           "tile_exact": int(spec.block_size % TILE_ROWS == 0 and tail == 0)}
    _family().labels(job=job, table=spec.config.table_id).set(
        row["tile_exact"])
    get_registry().gauge(
        "harmony_table_blocks",
        "Blocks of a tenant's dense model table (host work that walks the "
        "ownership map grows with it)",
        ("job", "table")).labels(
            job=job, table=spec.config.table_id).set(spec.num_blocks)
    if leaf_layout is not None:
        row["leaf_layout"] = dict(leaf_layout)
        get_registry().gauge(
            "harmony_table_leaf_pad_share",
            "Rows of a model table's section that hold no parameter / its "
            "rows: what giving every leaf whole row tiles costs",
            ("job", "table")).labels(
                job=job, table=spec.config.table_id).set(
                    leaf_layout["pad_rows"] / max(leaf_layout["rows"], 1))
    ledger().set_table_layout(job, row)
    return row


def _note_lowering(gauge, job: str, table_id: str, key: str,
                   lowering: str, engaged: str) -> None:
    from harmony_tpu.metrics.accounting import ledger

    gauge.labels(job=job, table=table_id).set(int(lowering == engaged))
    ledger().set_step_lowering(job, key, lowering)


def note_push(job: str, table_id: str, lowering: str) -> None:
    """Record what ``job``'s keyed push on ``table_id`` lowers to."""
    from harmony_tpu.metrics.registry import get_registry

    _note_lowering(get_registry().gauge(
        "harmony_table_push_pallas_rows",
        "1 when a tenant's keyed push is the in-place Pallas row "
        "scatter-add, 0 for XLA's scatter",
        ("job", "table")), job, table_id, "push_lowering", lowering,
        "pallas_rows")


def note_update(job: str, table_id: str, lowering: str) -> None:
    """Record how ``job``'s pull-all step applies its update to
    ``table_id``."""
    from harmony_tpu.metrics.registry import get_registry

    _note_lowering(get_registry().gauge(
        "harmony_table_update_row_ranges",
        "1 when a tenant's pull-all step runs its update rule in the push "
        "stage on the stored rows, section by section; 0 when it pushes a "
        "whole-table delta", ("job", "table")), job, table_id,
        "update_lowering", lowering, "row_ranges")


def note_fold(job: str, table_id: str, lowering: str,
              leaf_layout: Optional[Dict[str, int]] = None) -> None:
    """Record what the fold of ``job``'s row sections on ``table_id``
    lowers to (``row_ranges`` tenants) and, for a trainer with a
    ``leaf_layout`` (``LeafRows.record()``), the share of its gradient's
    rows that lowering reads from a leaf's own buffer."""
    from harmony_tpu.metrics.registry import get_registry

    if leaf_layout is not None:
        get_registry().gauge(
            "harmony_table_fold_direct_row_share",
            "Rows of a section's gradient the fold reads from one leaf's "
            "own buffer / the section's rows (0 where the fold is not the "
            "in-place kernel and the pieces are concatenated)",
            ("job", "table")).labels(job=job, table=table_id).set(
                leaf_layout["direct_rows"] / max(leaf_layout["rows"], 1)
                if lowering == "pallas_sections" else 0.0)

    _note_lowering(get_registry().gauge(
        "harmony_table_fold_pallas_sections",
        "1 when a tenant's update rule folds into its row sections in one "
        "in-place Pallas pass, 0 when XLA rewrites them",
        ("job", "table")), job, table_id, "fold_lowering", lowering,
        "pallas_sections")
