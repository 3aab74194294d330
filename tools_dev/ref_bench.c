/* Reference libdirac CPU timing at BASELINE.json's config 1.
 *
 * Times sagefit_visibilities (src/lib/Dirac/lmfit.c:778) on the same
 * problem shape as the cell cal-m8x3 (N=62 stations, M=8 clusters, one
 * chunk each, tilesz=10, solver mode SM_OSLM_OSRLM_RLBFGS = 3) with the
 * same iteration budget (max_emiter=3, max_iter=10, max_lbfgs=10, m=7).
 * Coherencies are synthetic (random smooth phases); data = J_true x coh
 * x J_true^H + noise, like io/dataset.simulate_dataset.
 *
 * Build (objects compiled from the read-only reference checkout):
 *   gcc -O3 -c <reference>/src/lib/Dirac/{...}.c && \
 *   gcc -O3 tools_dev/ref_bench.c *.o -o ref_bench \
 *       -llapack -lblas -lpthread -lm
 * Run: ./ref_bench [Nt]   (Nt = host threads, default nproc)
 * Prints one JSON line: {"config1_vis_per_sec": ..., "wall_s": ...}
 */

#include <complex.h>
#include <math.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>
#include <unistd.h>

#include "Dirac.h"

static double urand(void) { return (double)rand() / RAND_MAX; }
static double nrand(void) { /* Box-Muller */
  double u1 = urand() + 1e-12, u2 = urand();
  return sqrt(-2.0 * log(u1)) * cos(2.0 * M_PI * u2);
}

int main(int argc, char **argv) {
  const int N = 62, M = 8, tilesz = 10;
  const int Nbase0 = N * (N - 1) / 2;      /* baselines per timeslot */
  const int Nbase = Nbase0 * tilesz;       /* total rows */
  const int Mt = M;                        /* one chunk per cluster */
  const double freq0 = 150e6, fdelta = 180e3;
  int Nt = (argc > 1) ? atoi(argv[1]) : (int)sysconf(_SC_NPROCESSORS_ONLN);
  srand(17);

  baseline_t *barr = calloc(Nbase, sizeof(baseline_t));
  int row = 0;
  for (int t = 0; t < tilesz; t++)
    for (int i = 0; i < N; i++)
      for (int j = i + 1; j < N; j++) {
        barr[row].sta1 = i; barr[row].sta2 = j; barr[row].flag = 0; row++;
      }

  double *u = calloc(Nbase, sizeof(double));
  double *v = calloc(Nbase, sizeof(double));
  double *w = calloc(Nbase, sizeof(double));
  for (int i = 0; i < Nbase; i++) {
    u[i] = 1e-5 * nrand(); v[i] = 1e-5 * nrand(); w[i] = 1e-6 * nrand();
  }

  /* sky: 3 sources per cluster (only carr metadata matters to the solver;
     coherencies are precomputed below) */
  clus_source_t *carr = calloc(M, sizeof(clus_source_t));
  for (int m = 0; m < M; m++) {
    carr[m].N = 3; carr[m].id = m; carr[m].nchunk = 1;
    carr[m].p = calloc(1, sizeof(int));
    carr[m].p[0] = m * 8 * N;
  }

  /* coherencies: [row][cluster][4] complex, smooth random */
  complex double *coh = calloc((size_t)4 * M * Nbase, sizeof(complex double));
  for (int ci = 0; ci < Nbase; ci++)
    for (int cm = 0; cm < M; cm++) {
      double ph = 2.0 * M_PI * urand();
      double amp = 1.0 + 2.0 * urand();
      coh[4 * M * ci + 4 * cm + 0] = amp * cexp(I * ph);
      coh[4 * M * ci + 4 * cm + 1] = 0.1 * amp * cexp(I * ph * 0.5);
      coh[4 * M * ci + 4 * cm + 2] = 0.1 * amp * cexp(-I * ph * 0.5);
      coh[4 * M * ci + 4 * cm + 3] = amp * cexp(I * (ph + 0.2));
    }

  /* true Jones: diag-dominant random, one chunk per cluster */
  complex double *Jt = calloc((size_t)M * N * 4, sizeof(complex double));
  for (int i = 0; i < M * N * 4; i++)
    Jt[i] = 0.2 * (nrand() + I * nrand());
  for (int m = 0; m < M; m++)
    for (int s = 0; s < N; s++) {
      Jt[(m * N + s) * 4 + 0] += 1.0;
      Jt[(m * N + s) * 4 + 3] += 1.0;
    }

  /* data x: sum_m Jp C Jq^H + noise, [row][8] reals */
  double *x = calloc((size_t)8 * Nbase, sizeof(double));
  for (int ci = 0; ci < Nbase; ci++) {
    complex double V[4] = {0, 0, 0, 0};
    int p = barr[ci].sta1, q = barr[ci].sta2;
    for (int cm = 0; cm < M; cm++) {
      complex double *C = &coh[4 * M * ci + 4 * cm];
      complex double *Jp = &Jt[(cm * N + p) * 4];
      complex double *Jq = &Jt[(cm * N + q) * 4];
      complex double T[4];
      T[0] = Jp[0] * C[0] + Jp[1] * C[2];
      T[1] = Jp[0] * C[1] + Jp[1] * C[3];
      T[2] = Jp[2] * C[0] + Jp[3] * C[2];
      T[3] = Jp[2] * C[1] + Jp[3] * C[3];
      V[0] += T[0] * conj(Jq[0]) + T[1] * conj(Jq[1]);
      V[1] += T[0] * conj(Jq[2]) + T[1] * conj(Jq[3]);
      V[2] += T[2] * conj(Jq[0]) + T[3] * conj(Jq[1]);
      V[3] += T[2] * conj(Jq[2]) + T[3] * conj(Jq[3]);
    }
    for (int k = 0; k < 4; k++) {
      x[8 * ci + 2 * k] = creal(V[k]) + 0.01 * nrand();
      x[8 * ci + 2 * k + 1] = cimag(V[k]) + 0.01 * nrand();
    }
  }

  /* initial solutions: identity Jones */
  double *pp = calloc((size_t)8 * N * Mt, sizeof(double));
  for (int m = 0; m < Mt; m++)
    for (int s = 0; s < N; s++) {
      pp[m * 8 * N + s * 8 + 0] = 1.0;   /* re J00 */
      pp[m * 8 * N + s * 8 + 6] = 1.0;   /* re J11 (README.md:188 layout) */
    }

  double mean_nu = 0, res_0 = 0, res_1 = 0;
  /* one warm call is pointless on CPU (no compile step): time directly */
  struct timespec t0, t1;
  const int reps = 1;
  clock_gettime(CLOCK_MONOTONIC, &t0);
  for (int r = 0; r < reps; r++) {
    /* fresh start each rep, like the bench's repeated jitted step */
    for (int m = 0; m < Mt; m++)
      for (int s = 0; s < N; s++) {
        memset(&pp[m * 8 * N + s * 8], 0, 8 * sizeof(double));
        pp[m * 8 * N + s * 8 + 0] = 1.0;
        pp[m * 8 * N + s * 8 + 6] = 1.0;
      }
    sagefit_visibilities(u, v, w, x, N, Nbase0, tilesz, barr, carr, coh, M,
                         Mt, freq0, fdelta, pp, 0.0, Nt,
                         /*max_emiter*/ 3, /*max_iter*/ 10,
                         /*max_lbfgs*/ 10, /*lbfgs_m*/ 7,
                         /*gpu_threads*/ 0, /*linsolv*/ 1,
                         /*solver_mode*/ SM_OSLM_OSRLM_RLBFGS,
                         /*nulow*/ 2.0, /*nuhigh*/ 30.0, /*randomize*/ 1,
                         &mean_nu, &res_0, &res_1);
  }
  clock_gettime(CLOCK_MONOTONIC, &t1);
  double dt = (t1.tv_sec - t0.tv_sec) + 1e-9 * (t1.tv_nsec - t0.tv_nsec);
  dt /= reps;
  printf("{\"config1_vis_per_sec\": %.1f, \"wall_s\": %.3f, "
         "\"res_0\": %.6g, \"res_1\": %.6g, \"threads\": %d, "
         "\"note\": \"reference libdirac sagefit_visibilities, mode "
         "SM_OSLM_OSRLM_RLBFGS (-j 3), "
         "N=62 M=8 tilesz=10, emiter=3 iter=10 lbfgs=10\"}\n",
         (double)Nbase / dt, dt, res_0, res_1, Nt);
  return 0;
}
