package org.apache.spark.shuffle.tpu;

import java.io.DataInputStream;
import java.io.DataOutputStream;
import java.io.IOException;
import java.net.Socket;
import java.nio.ByteBuffer;
import java.nio.ByteOrder;
import java.nio.charset.StandardCharsets;

/**
 * Client for the TPU shuffle daemon protocol (docs/SHIM_PROTOCOL.md).
 *
 * Frame layout (little-endian): u32 op | u64 headerLen | u64 bodyLen | header | body.
 * Control headers are JSON; the batched fetch (op 3/4) uses the binary batch
 * header of the AM protocol. The Python twin of this class is
 * sparkucx_tpu.shuffle.daemon.DaemonClient, which is covered by tests.
 */
public final class DaemonClient implements AutoCloseable {
  public static final int OP_CREATE_SHUFFLE = 16;
  public static final int OP_OPEN_MAP_WRITER = 17;
  public static final int OP_WRITE_PARTITION = 18;
  public static final int OP_COMMIT_MAP = 19;
  public static final int OP_RUN_EXCHANGE = 20;
  public static final int OP_REMOVE_SHUFFLE = 21;
  public static final int OP_FETCH = 3;          // AM FetchBlockReq
  public static final int OP_FETCH_ACK = 4;      // AM FetchBlockReqAck

  /** Shared frame ceiling — MUST equal MAX_FRAME_BYTES in
   * sparkucx_tpu/core/definitions.py (the daemon drops any connection whose
   * frame claims more; fixture 10_oversized_frame.bin pins both sides). */
  public static final long MAX_FRAME_BYTES = 1L << 31;

  /** Bytes of one map writer's blocks that go out as ONE WritePartition frame
   * (writePartitions); the value of WRITE_BATCH_BYTES in
   * sparkucx_tpu/shuffle/daemon.py, fixed there by a probe on the chip's host.
   * The daemon knows no bound: a frame may carry more or less. */
  public static final long WRITE_BATCH_BYTES = 64L << 20;

  /** True when a frame header's declared sizes exceed the shared ceiling —
   * the reject condition both the daemon and this client apply before
   * allocating anything.  Written without the naive sum so two huge positive
   * lengths cannot wrap the long negative and sneak past the guard. */
  static boolean frameTooLarge(long headerLen, long bodyLen) {
    return headerLen < 0 || bodyLen < 0
        || headerLen > MAX_FRAME_BYTES
        || bodyLen > MAX_FRAME_BYTES - headerLen;
  }

  private final Socket socket;
  private final DataOutputStream out;
  private final DataInputStream in;

  public DaemonClient(String host, int port) throws IOException {
    this.socket = new Socket(host, port);
    this.socket.setTcpNoDelay(true);
    this.out = new DataOutputStream(socket.getOutputStream());
    this.in = new DataInputStream(socket.getInputStream());
  }

  /**
   * Pure frame encoder: u32 op | u64 headerLen | u64 bodyLen | header | body,
   * little-endian. Exposed static so the golden wire fixtures
   * (jvm/fixtures, FixtureCheck.java, tests/test_daemon.py) byte-check the
   * exact encoding without a socket.
   */
  static byte[] encodeFrame(int op, String jsonHeader, byte[] body) {
    byte[] header = jsonHeader == null ? new byte[0] : jsonHeader.getBytes(StandardCharsets.UTF_8);
    byte[] payload = body == null ? new byte[0] : body;
    ByteBuffer bb = ByteBuffer.allocate(20 + header.length + payload.length)
        .order(ByteOrder.LITTLE_ENDIAN);
    bb.putInt(op).putLong(header.length).putLong(payload.length);
    bb.put(header).put(payload);
    return bb.array();
  }

  // JSON header builders — the exact bytes each op puts on the wire, shared by
  // the client methods and FixtureCheck so a format drift fails the fixtures.
  static String headerCreateShuffle(int shuffleId, int numMappers, int numReducers) {
    return String.format("{\"shuffle_id\": %d, \"num_mappers\": %d, \"num_reducers\": %d}",
        shuffleId, numMappers, numReducers);
  }

  static String headerOpenMapWriter(int shuffleId, int mapId) {
    return String.format("{\"shuffle_id\": %d, \"map_id\": %d}", shuffleId, mapId);
  }

  static String headerWritePartition(int writer, int reduceId) {
    return String.format("{\"writer\": %d, \"reduce_id\": %d}", writer, reduceId);
  }

  /** The several-block form of WritePartition: blocks of ONE writer, reduce
   * ids non-decreasing (consecutive equal ids continue one partition), the
   * body the blocks back to back in this order. */
  static String headerWritePartitions(int writer, int[] reduceIds, int[] lengths) {
    return String.format("{\"writer\": %d, \"reduce_ids\": %s, \"lengths\": %s}",
        writer, jsonInts(reduceIds), jsonInts(lengths));
  }

  /** json.dumps of a list of ints: "[1, 2, 3]". */
  static String jsonInts(int[] values) {
    StringBuilder sb = new StringBuilder("[");
    for (int i = 0; i < values.length; i++) {
      if (i > 0) sb.append(", ");
      sb.append(values[i]);
    }
    return sb.append("]").toString();
  }

  /** The blocks back to back: the body of a several-block WritePartition. */
  static byte[] joinBlocks(byte[][] blocks) {
    int total = 0;
    for (byte[] b : blocks) total += b.length;
    byte[] body = new byte[total];
    int pos = 0;
    for (byte[] b : blocks) {
      System.arraycopy(b, 0, body, pos, b.length);
      pos += b.length;
    }
    return body;
  }

  static String headerCommitMap(int writer) {
    return String.format("{\"writer\": %d}", writer);
  }

  static String headerShuffleId(int shuffleId) {
    return String.format("{\"shuffle_id\": %d}", shuffleId);
  }

  /** Batched fetch request body: u64 tag | u32 count | (i32 shuffle, i32 map, i32 reduce)*n. */
  static byte[] fetchRequestBody(long tag, int shuffleId, int[] mapIds, int[] reduceIds) {
    int n = mapIds.length;
    ByteBuffer req = ByteBuffer.allocate(12 + 12 * n).order(ByteOrder.LITTLE_ENDIAN);
    req.putLong(tag);
    req.putInt(n);
    for (int i = 0; i < n; i++) {
      req.putInt(shuffleId).putInt(mapIds[i]).putInt(reduceIds[i]);
    }
    return req.array();
  }

  private synchronized byte[][] call(int op, String jsonHeader, byte[] body) throws IOException {
    out.write(encodeFrame(op, jsonHeader, body));
    out.flush();
    byte[] frameHeader = new byte[20];
    in.readFully(frameHeader);
    ByteBuffer bb = ByteBuffer.wrap(frameHeader).order(ByteOrder.LITTLE_ENDIAN);
    bb.getInt(); // reply op
    long hlenL = bb.getLong();
    long blenL = bb.getLong();
    // the shared wire ceiling, plus the JVM's own array bound: a frame AT
    // the 2 GiB limit is wire-legal but not int-addressable here, so it gets
    // the same controlled close instead of a NegativeArraySizeException
    if (frameTooLarge(hlenL, blenL)
        || hlenL > Integer.MAX_VALUE || blenL > Integer.MAX_VALUE) {
      socket.close();
      throw new IOException(
          "reply frame too large (header " + hlenL + " + body " + blenL
              + " B vs limit " + MAX_FRAME_BYTES + ")");
    }
    int hlen = (int) hlenL;
    int blen = (int) blenL;
    byte[] replyHeader = new byte[hlen];
    byte[] replyBody = new byte[blen];
    in.readFully(replyHeader);
    in.readFully(replyBody);
    return new byte[][] {replyHeader, replyBody};
  }

  private byte[][] controlCall(int op, String jsonHeader, byte[] body) throws IOException {
    byte[][] reply = call(op, jsonHeader, body);
    String ack = new String(reply[0], StandardCharsets.UTF_8);
    if (!ack.contains("\"ok\": true") && !ack.contains("\"ok\":true")) {
      throw new IOException("daemon error: " + ack);
    }
    return reply;
  }

  public void createShuffle(int shuffleId, int numMappers, int numReducers) throws IOException {
    controlCall(OP_CREATE_SHUFFLE, headerCreateShuffle(shuffleId, numMappers, numReducers), null);
  }

  public int openMapWriter(int shuffleId, int mapId) throws IOException {
    byte[][] reply = controlCall(OP_OPEN_MAP_WRITER, headerOpenMapWriter(shuffleId, mapId), null);
    String ack = new String(reply[0], StandardCharsets.UTF_8);
    // ack is json.dumps output: {"ok": true, "writer": N} — skip the space
    // after the colon, then take the digit run
    int p = ack.indexOf("\"writer\":") + 9;
    while (p < ack.length() && !Character.isDigit(ack.charAt(p))) p++;
    int q = p;
    while (q < ack.length() && Character.isDigit(ack.charAt(q))) q++;
    if (p == q) throw new IOException("malformed OpenMapWriter ack: " + ack);
    return Integer.parseInt(ack.substring(p, q));
  }

  public void writePartition(int writer, int reduceId, byte[] data, int off, int len)
      throws IOException {
    byte[] chunk = new byte[len];
    System.arraycopy(data, off, chunk, 0, len);
    controlCall(OP_WRITE_PARTITION, headerWritePartition(writer, reduceId), chunk);
  }

  /**
   * Several blocks of one map writer in ONE frame and one round trip (the
   * daemon still acknowledges every block: the ack's "written" list, checked
   * here against the lengths sent).  A block the daemon refuses ends the frame
   * there: the IOException carries its ack — the error, the refused
   * "reduce_id" and the blocks "written" before it — and the map task fails
   * before its commit, to be retried whole.
   */
  public void writePartitions(int writer, int[] reduceIds, byte[][] blocks) throws IOException {
    int[] lengths = new int[blocks.length];
    for (int i = 0; i < blocks.length; i++) lengths[i] = blocks[i].length;
    byte[][] reply = controlCall(OP_WRITE_PARTITION,
        headerWritePartitions(writer, reduceIds, lengths), joinBlocks(blocks));
    String ack = new String(reply[0], StandardCharsets.UTF_8);
    String want = "\"written\": " + jsonInts(lengths);
    if (!ack.contains(want)) {
      throw new IOException("daemon acked other blocks than were sent: " + ack + " for " + want);
    }
  }

  public long[] commitMap(int writer) throws IOException {
    byte[][] reply = controlCall(OP_COMMIT_MAP, headerCommitMap(writer), null);
    ByteBuffer bb = ByteBuffer.wrap(reply[1]).order(ByteOrder.LITTLE_ENDIAN);
    long[] lengths = new long[reply[1].length / 8];
    for (int i = 0; i < lengths.length; i++) lengths[i] = bb.getLong();
    return lengths;
  }

  public void runExchange(int shuffleId) throws IOException {
    controlCall(OP_RUN_EXCHANGE, headerShuffleId(shuffleId), null);
  }

  /** Batched fetch: returns one byte[] per requested block; null marks a miss. */
  public byte[][] fetchBlocks(int shuffleId, int[] mapIds, int[] reduceIds) throws IOException {
    byte[][] reply = call(OP_FETCH, null, fetchRequestBody(0L, shuffleId, mapIds, reduceIds));
    ByteBuffer hdr = ByteBuffer.wrap(reply[0]).order(ByteOrder.LITTLE_ENDIAN);
    hdr.getLong();             // tag echo
    int count = hdr.getInt();
    long[] sizes = new long[count];
    for (int i = 0; i < count; i++) sizes[i] = hdr.getLong();
    byte[][] blocks = new byte[count][];
    int pos = 0;
    for (int i = 0; i < count; i++) {
      if (sizes[i] < 0) { blocks[i] = null; continue; }
      blocks[i] = new byte[(int) sizes[i]];
      System.arraycopy(reply[1], pos, blocks[i], 0, (int) sizes[i]);
      pos += (int) sizes[i];
    }
    return blocks;
  }

  public void removeShuffle(int shuffleId) throws IOException {
    controlCall(OP_REMOVE_SHUFFLE, headerShuffleId(shuffleId), null);
  }

  @Override
  public void close() throws IOException {
    socket.close();
  }
}
