/* Plain reference in C: the same semantics as benchmark/reference.py,
 * fast enough for the 20x20 rows. It includes nothing of the program.
 *
 * Taillard's instances (Taillard 1993, EJOR 64:278-285) and an exact
 * depth-first branch and bound for the permutation flow-shop with the
 * LB1 (one-machine) and LB2 (two-machine Johnson, Lageweg, Lenstra and
 * Rinnooy Kan 1978) bounds, as the sequential C programs of
 * ivantag13/dist-GPU-accelerated-tree-search define them.
 *
 *     cc -O2 -o reference_c benchmark/reference.c
 *     ./reference_c <inst> <lb> [ub]     one JSON line: tree, sol, best
 *     ./reference_c --instance <inst>    the processing times, one
 *                                        machine per line
 *
 * The incumbent starts at `ub` (Taillard's optimum when left out).
 * Counting follows reference.py: a child whose bound is below the
 * incumbent is pushed and counted in `tree` (the root is not); a leaf
 * child is counted in `sol` and its makespan becomes the incumbent when
 * lower. As in reference.py, every child of a node is bounded before
 * any is explored, and the last child is explored first. An LB2 bound
 * that is not a leaf's stops at the first machine pair that reaches the
 * incumbent: only its comparison with the incumbent is used.
 */
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>

#define MAXM 20
#define MAXN 20
#define MAXP (MAXM * (MAXM - 1) / 2)

static const long TIME_SEEDS[30] = {
    873654221, 379008056, 1866992158, 216771124, 495070989,
    402959317, 1369363414, 2021925980, 573109518, 88325120,
    587595453, 1401007982, 873136276, 268827376, 1634173168,
    691823909, 73807235, 1273398721, 2065119309, 1672900551,
    479340445, 268827376, 1958948863, 918272953, 555010963,
    2010851491, 1519833303, 1748670931, 1923497586, 1829909967,
};
static const int OPTIMA[30] = {
    1278, 1359, 1081, 1293, 1235, 1195, 1234, 1206, 1230, 1108,
    1582, 1659, 1496, 1377, 1419, 1397, 1484, 1538, 1593, 1591,
    2297, 2099, 2326, 2223, 2291, 2226, 2273, 2200, 2237, 2178,
};

static int M, N, P, LB;
static int p[MAXM][MAXN];
static int total[MAXM];
static int min_tails[MAXM];
static int ma0[MAXP], ma1[MAXP];
static int lags[MAXP][MAXN];
static int order[MAXP][MAXN];
static int best;
static long long tree, sol;

static void instance(int inst)
{
    long seed = TIME_SEEDS[inst - 1];
    M = inst <= 10 ? 5 : inst <= 20 ? 10 : 20;
    N = 20;
    for (int i = 0; i < M; i++)
        for (int j = 0; j < N; j++) {
            long k = seed / 127773;
            seed = 16807 * (seed % 127773) - k * 2836;
            if (seed < 0)
                seed += 2147483647;
            float q = (float)seed / (float)2147483647;
            p[i][j] = 1 + (int)((double)q * 99.0);
        }
}

/* Johnson's order of one pair: jobs faster on the first machine by
 * ascending first time, then the rest by descending second time; ties
 * by job index. */
static int key_first[MAXN], key_val[MAXN];

static int johnson_cmp(const void *x, const void *y)
{
    int a = *(const int *)x, b = *(const int *)y;
    if (key_first[a] != key_first[b])
        return key_first[a] ? -1 : 1;
    if (key_val[a] != key_val[b])
        return key_val[a] < key_val[b] ? -1 : 1;
    return a - b;
}

static void tables(void)
{
    int tails[MAXM + 1][MAXN];
    memset(tails, 0, sizeof tails);
    for (int k = M - 1; k >= 0; k--)
        for (int j = 0; j < N; j++)
            tails[k][j] = tails[k + 1][j] + p[k][j];
    for (int k = 0; k < M; k++) {
        total[k] = 0;
        for (int j = 0; j < N; j++)
            total[k] += p[k][j];
        min_tails[k] = 0;
        if (k < M - 1) {
            min_tails[k] = tails[k + 1][0];
            for (int j = 1; j < N; j++)
                if (tails[k + 1][j] < min_tails[k])
                    min_tails[k] = tails[k + 1][j];
        }
    }
    P = 0;
    for (int a = 0; a < M - 1; a++)
        for (int b = a + 1; b < M; b++, P++) {
            ma0[P] = a;
            ma1[P] = b;
            for (int j = 0; j < N; j++) {
                int lag = 0;
                for (int k = a + 1; k < b; k++)
                    lag += p[k][j];
                lags[P][j] = lag;
                int t0 = p[a][j] + lag, t1 = p[b][j] + lag;
                key_first[j] = t0 < t1;
                key_val[j] = t0 < t1 ? t0 : -t1;
                order[P][j] = j;
            }
            qsort(order[P], N, sizeof(int), johnson_cmp);
        }
}

static int lb1(const int *front, const int *remain)
{
    int tmp = front[0] + remain[0];
    int lb = tmp + min_tails[0];
    for (int i = 1; i < M; i++) {
        if (front[i] + remain[i] > tmp)
            tmp = front[i] + remain[i];
        if (tmp + min_tails[i] > lb)
            lb = tmp + min_tails[i];
    }
    return lb;
}

/* `stop`: return as soon as the bound reaches it (INT_MAX: exact). */
static int lb2(const int *front, const unsigned char *unsched, int stop)
{
    int lb = 0;
    for (int q = 0; q < P; q++) {
        int a = ma0[q], b = ma1[q];
        int t0 = front[a], t1 = front[b];
        for (int k = 0; k < N; k++) {
            int job = order[q][k];
            if (!unsched[job])
                continue;
            t0 += p[a][job];
            int r = t0 + lags[q][job];
            t1 = (t1 > r ? t1 : r) + p[b][job];
        }
        int v = t1 + min_tails[b];
        if (t0 + min_tails[a] > v)
            v = t0 + min_tails[a];
        if (v > lb)
            lb = v;
        if (lb >= stop)
            return lb;
    }
    return lb;
}

static void dfs(int *perm, int depth, const int *front,
                const int *done_work, unsigned char *unsched)
{
    int c = N - depth;
    int fr[MAXN][MAXM];
    int keep[MAXN], nkeep = 0;
    for (int i = 0; i < c; i++) {
        int job = perm[depth + i];
        int *f = fr[i];
        f[0] = front[0] + p[0][job];
        for (int k = 1; k < M; k++)
            f[k] = (f[k - 1] > front[k] ? f[k - 1] : front[k]) + p[k][job];
        int leaf = depth + 1 == N;
        int bound;
        if (LB == 1) {
            int remain[MAXM] = {0};
            for (int k = 0; k < M; k++)
                remain[k] = total[k] - done_work[k] - p[k][job];
            bound = lb1(f, remain);
        } else {
            unsched[job] = 0;
            bound = lb2(f, unsched, leaf ? 0x7fffffff : best);
            unsched[job] = 1;
        }
        if (leaf) {
            sol++;
            if (bound < best)
                best = bound;
        } else if (bound < best) {
            keep[nkeep++] = i;
            tree++;
        }
    }
    for (int t = nkeep - 1; t >= 0; t--) {
        int i = keep[t];
        int job = perm[depth + i];
        int work[MAXM];
        for (int k = 0; k < M; k++)
            work[k] = done_work[k] + p[k][job];
        perm[depth + i] = perm[depth];
        perm[depth] = job;
        unsched[job] = 0;
        dfs(perm, depth + 1, fr[i], work, unsched);
        unsched[job] = 1;
        perm[depth] = perm[depth + i];
        perm[depth + i] = job;
    }
}

int main(int argc, char **argv)
{
    if (argc == 3 && strcmp(argv[1], "--instance") == 0) {
        int inst = atoi(argv[2]);
        if (inst < 1 || inst > 30)
            return 2;
        instance(inst);
        for (int i = 0; i < M; i++)
            for (int j = 0; j < N; j++)
                printf("%d%c", p[i][j], j == N - 1 ? '\n' : ' ');
        return 0;
    }
    if (argc < 3 || argc > 4) {
        fprintf(stderr, "usage: %s <inst> <lb> [ub] | --instance <inst>\n",
                argv[0]);
        return 2;
    }
    int inst = atoi(argv[1]);
    LB = atoi(argv[2]);
    if (inst < 1 || inst > 30 || (LB != 1 && LB != 2))
        return 2;
    instance(inst);
    tables();
    best = argc == 4 ? atoi(argv[3]) : OPTIMA[inst - 1];
    int perm[MAXN], front[MAXM] = {0}, work[MAXM] = {0};
    unsigned char unsched[MAXN];
    for (int j = 0; j < N; j++) {
        perm[j] = j;
        unsched[j] = 1;
    }
    struct timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);
    dfs(perm, 0, front, work, unsched);
    clock_gettime(CLOCK_MONOTONIC, &t1);
    printf("{\"inst\": %d, \"lb\": %d, \"tree\": %lld, \"sol\": %lld, "
           "\"best\": %d, \"seconds\": %.3f}\n", inst, LB, tree, sol, best,
           (double)(t1.tv_sec - t0.tv_sec) + 1e-9 * (t1.tv_nsec - t0.tv_nsec));
    return 0;
}
