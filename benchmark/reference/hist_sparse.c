/* Plain histogram loop over sparse rows for benchmark/reference/gbdt_sparse.py.
 *
 * For every row r of `rows` (all rows when rows is NULL) and every entry e
 * of that row, indptr[r] <= e < indptr[r + 1]:
 *     out[base[indices[e]] + bins[e]] += (g[r], h[r], 1),
 * base[j] the cell of column j's bin 0 in the caller's layout (a column
 * holds the bins it uses and no more, so `out` stays in the cache).
 * Cells without an entry are not visited: the caller adds a column's
 * implicit bin from the node's totals.  Nothing of the program under test
 * is used; gbdt_sparse.py falls back to numpy's bincount, which gives the
 * same sums, where no C compiler is at hand.
 */
#include <stdint.h>
#include <stddef.h>

void node_hist_sparse(const int64_t *indptr, const int32_t *indices,
                      const uint8_t *bins, const int64_t *rows,
                      int64_t n_rows, const double *g, const double *h,
                      const int64_t *base, double *out)
{
    for (int64_t i = 0; i < n_rows; ++i) {
        int64_t r = rows ? rows[i] : i;
        double gi = g[r], hi = h[r];
        for (int64_t e = indptr[r]; e < indptr[r + 1]; ++e) {
            double *o = out + (base[indices[e]] + bins[e]) * 3;
            o[0] += gi;
            o[1] += hi;
            o[2] += 1.0;
        }
    }
}
