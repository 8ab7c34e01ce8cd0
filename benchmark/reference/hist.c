/* Plain histogram loop for benchmark/reference/gbdt.py.
 *
 * out[(f - f0) * num_bins + bins[r, f]] += (g[r], h[r], 1) for every row r
 * of `rows` (all rows when rows is NULL) and every feature f in [f0, f1).
 * Nothing of the program under test is used; gbdt.py falls back to numpy's
 * bincount, which gives the same sums, where no C compiler is at hand.
 */
#include <stdint.h>
#include <stddef.h>

void node_hist(const uint8_t *bins, int64_t n_features, const int64_t *rows,
               int64_t n_rows, const double *g, const double *h,
               int64_t f0, int64_t f1, int64_t num_bins, double *out)
{
    for (int64_t i = 0; i < n_rows; ++i) {
        int64_t r = rows ? rows[i] : i;
        const uint8_t *b = bins + (size_t)r * (size_t)n_features;
        double gi = g[r], hi = h[r];
        for (int64_t f = f0; f < f1; ++f) {
            double *o = out + ((f - f0) * num_bins + b[f]) * 3;
            o[0] += gi;
            o[1] += hi;
            o[2] += 1.0;
        }
    }
}
