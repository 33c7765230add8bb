"""Synthetic RBAC with nested groups: users in groups, groups nested in
groups ``group_levels`` deep, groups in roles, roles nested in roles, and
resource grants to roles or groups (Zanzibar's userset nesting).

Groups are split into ``group_levels`` equal levels; a nesting tuple puts
a group of level l into a group of level l + 1, so the longest chain of
groups is ``group_levels`` long. Shares of the tuples (``mix``): users in
groups of any level, group nesting, groups in roles, roles in roles (at
most half of all role pairs), and the rest grants, topped up until the
store holds exactly ``tuples`` distinct tuples. Drawn with numpy from
the seed.
"""

from __future__ import annotations

from ..graph import Graph, Kind, fill, pick


def build(sizes: dict, rng) -> Graph:
    n = int(sizes["tuples"])
    ns = sizes["namespace"]
    users = Kind("user", 0, int(sizes["users"]), ("u{}",))
    groups = Kind("group", users.stop, int(sizes["groups"]),
                  (ns, "g{}", "member"))
    roles = Kind("role", groups.stop, int(sizes["roles"]),
                 (ns, "role{}", "member"))
    resources = Kind("resource", roles.stop, int(sizes["resources"]),
                     (ns, "res{}", "view"))
    mix = sizes["mix"]
    levels = int(sizes["group_levels"])
    per_level = groups.count // levels

    def nesting(k):
        # group (level l + 1) gets member group (level l)
        lv = groups.start + rng.integers(levels - 1, size=k) * per_level
        inner = lv + rng.integers(per_level, size=k)
        outer = lv + per_level + rng.integers(per_level, size=k)
        return outer, inner

    def grant(rng, k):
        # a grant goes to a role or a group, uniform over both
        dst = groups.start + rng.integers(groups.count + roles.count, size=k)
        return pick(resources, rng, k), dst

    k_rr = min(int(n * mix["role_role"]), roles.count * roles.count // 2)
    stages = [
        (pick(groups, rng, int(n * mix["membership"])),
         pick(users, rng, int(n * mix["membership"]))),
        nesting(int(n * mix["group_nesting"])),
        (pick(roles, rng, int(n * mix["group_role"])),
         pick(groups, rng, int(n * mix["group_role"]))),
        (pick(roles, rng, k_rr), pick(roles, rng, k_rr)),
    ]
    src, dst = fill(stages, grant, n, resources.stop, rng)
    return Graph(
        kinds=[users, groups, roles, resources],
        src=src,
        dst=dst,
        objects="resource",
        subjects="user",
        grants=("resource",),
    )
