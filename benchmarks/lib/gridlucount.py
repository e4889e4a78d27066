"""Operations and bytes the panel factorizations of the pivoted LU on
a grid NEED at the heights they ran (lib/opcount.py holds the whole
solve's, `gesv`). f32 words.

`lu._lu_scan_grid` factors every panel on EVERY chip alike, from a
replicated copy of its column block: the count is one chip's, and so
is the peak it is held against. A panel of m rows and w columns needs
w^2 (m - w/3) flops and reads and writes the panel once; the program
counts the rows its panels were factored over
(`grid.lu_panel_rows_factored`: a stage's height at each of its
steps), so the count needs no copy of its stage plan."""


def panel_factors(n, w, rows_factored, word=4):
    """(flops, bytes) of the n / w panel factorizations of one solve,
    their heights summing to `rows_factored`."""
    steps = n // w
    return (w * w * (rows_factored - steps * w / 3.0),
            2.0 * word * w * rows_factored)
