#!/usr/bin/env bash
# Run the tier-1 suite in N sequential pytest chunks.
#
# For a host without the cores for the xdist tier-1 run (`-n 6`): one
# invocation on one core cannot fit the cap, as XLA:CPU compiles
# dominate. Splitting the test FILES round-robin into N chunks keeps
# every invocation under the cap while preserving the exact same
# selection (-m 'not slow'); the persistent .jax_cache is shared across
# chunks, so compile work is paid once. Round-robin (not contiguous) so
# the alphabetical cluster of compile-heavy device suites (test_bl_*,
# test_pallas_*, ...) spreads across chunks.
#
# Usage:
#   tools/tier1_chunks.sh [N] [--list] [extra pytest args...]
# Env:
#   CHUNK_TIMEOUT  seconds per chunk (default 870, the tier-1 cap)
#
# --list prints the chunk -> file assignment (one line per chunk) and
# exits 0 without running anything, so a CI log's chunked verdicts are
# auditable against exactly which files each chunk covered. It also
# flags any KNOWN-CONFLICTING pair that still shares a chunk (only
# possible at N=1).
#
# Known-conflicting pairs (CONFLICTS below) are separated STRUCTURALLY:
# after the round-robin assignment, the later member of a pair that
# landed in the same chunk is moved to the next chunk — the PR-10 note
# (test_daemon + test_mock_and_scale contention-flake the reshare
# timeout when run back to back on the 1-core box) no longer depends
# on round-robin luck as the file list grows.
#
# Registration is by glob: every tests/test_*.py is picked up
# automatically. File order only spreads files over chunks: the
# single-invocation run (`-n 6 --dist load`) orders its work with the
# scheduler in tests/conftest.py (GRAPH_GROUPS: device-graph groups
# first, each on one worker), so no suite needs a late-alphabet name.
#
# Exit status: 0 iff every chunk passed.

set -u
cd "$(dirname "$0")/.."

# pairs that must never share a chunk (space-separated file names);
# keep each pair alphabetically ordered — the SECOND member moves
CONFLICTS=(
    "tests/test_daemon.py tests/test_mock_and_scale.py"
)

# first arg is N only when it is a positive integer — otherwise it is a
# pytest arg and the default chunk count applies (a bad N must never
# yield a zero-iteration loop that exits 0 without running anything);
# --list is accepted before or after N
N=4
LIST=0
if [ "${1:-}" = "--list" ]; then
    LIST=1
    shift
fi
if [[ "${1:-}" =~ ^[0-9]+$ ]] && [ "$1" -ge 1 ]; then
    N=$1
    shift
fi
if [ "${1:-}" = "--list" ]; then
    LIST=1
    shift
fi

FILES=()
while IFS= read -r f; do FILES+=("$f"); done < <(ls tests/test_*.py | sort)

# round-robin assignment: chunk_of[i] = i % N
chunk_of=()
for ((i = 0; i < ${#FILES[@]}; i++)); do
    chunk_of[i]=$((i % N))
done

# find_pair_indices <a> <b>: sets PAIR_IA/PAIR_IB to the FILES indices
# (-1 when absent) — the one pair-matching rule, shared by the resolver
# and the flagger so they can never diverge
find_pair_indices() {
    PAIR_IA=-1 PAIR_IB=-1
    local i
    for ((i = 0; i < ${#FILES[@]}; i++)); do
        [ "${FILES[i]}" = "$1" ] && PAIR_IA=$i
        [ "${FILES[i]}" = "$2" ] && PAIR_IB=$i
    done
}

# structural conflict separation: move the later member of a
# same-chunk conflicting pair to the next chunk (deterministic; a
# no-op when round-robin already separated them or N=1)
if [ "$N" -gt 1 ]; then
    for pair in "${CONFLICTS[@]}"; do
        read -r a b <<<"$pair"
        find_pair_indices "$a" "$b"
        if [ "$PAIR_IA" -ge 0 ] && [ "$PAIR_IB" -ge 0 ] &&
            [ "${chunk_of[PAIR_IA]}" -eq "${chunk_of[PAIR_IB]}" ]; then
            chunk_of[PAIR_IB]=$(((chunk_of[PAIR_IB] + 1) % N))
        fi
    done
fi

# flag any conflicting pair still sharing a chunk (N=1, or a future
# three-way conflict the one-step move cannot untangle)
flag_conflicts() {
    local rc=0
    for pair in "${CONFLICTS[@]}"; do
        read -r a b <<<"$pair"
        find_pair_indices "$a" "$b"
        if [ "$PAIR_IA" -ge 0 ] && [ "$PAIR_IB" -ge 0 ] &&
            [ "${chunk_of[PAIR_IA]}" -eq "${chunk_of[PAIR_IB]}" ]; then
            echo "WARNING: known-conflicting pair in one chunk" \
                "($((chunk_of[PAIR_IA] + 1))/$N): $a + $b" >&2
            rc=1
        fi
    done
    return $rc
}

if [ "$LIST" -eq 1 ]; then
    for ((c = 0; c < N; c++)); do
        chunk=()
        for ((i = 0; i < ${#FILES[@]}; i++)); do
            [ "${chunk_of[i]}" -eq "$c" ] && chunk+=("${FILES[i]}")
        done
        echo "chunk $((c + 1))/$N: ${chunk[*]:-}"
    done
    flag_conflicts
    exit 0
fi

flag_conflicts || true

fail=0
for ((c = 0; c < N; c++)); do
    chunk=()
    for ((i = 0; i < ${#FILES[@]}; i++)); do
        [ "${chunk_of[i]}" -eq "$c" ] && chunk+=("${FILES[i]}")
    done
    [ ${#chunk[@]} -eq 0 ] && continue
    echo "=== chunk $((c + 1))/$N: ${chunk[*]}" >&2
    timeout -k 10 "${CHUNK_TIMEOUT:-870}" \
        env JAX_PLATFORMS=cpu python -m pytest "${chunk[@]}" -q \
        -m 'not slow' --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly "$@"
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "=== chunk $((c + 1))/$N FAILED (rc=$rc)" >&2
        fail=1
    fi
done
exit $fail
