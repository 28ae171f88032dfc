"""Operations and bytes that a step or a kernel needs, from shapes and the
batch's unique-id count — the same whatever implements the work (a row
group moved to reach one row counts as the one row)."""
